"""Wave grower — leaf-wise growth with NO physical row movement.

The partitioned grower (learner/partitioned.py) keeps rows leaf-contiguous
so per-split histogram work scales with the split leaf's size; the price is
moving every row once per level it participates in (~37 ns/row via the
1-bit-sort partition — 55-60%% of tree time at Higgs scale, PERF.md).  This
grower removes that cost entirely by exploiting the MXU's lane dimension
instead: the leaf-batched Pallas kernel
(ops/histogram_pallas.py ``build_histogram_pallas_leaves``) computes
**LEAF_CHANNELS=25 leaf histograms in one full-data pass** for the cost of
one — the single-leaf kernel wastes 123 of the 128 output lanes of its
one-hot contraction, so 25 leaves x 5 weight channels (125 lanes) fill
them instead.

Growth proceeds in *waves*: each wave splits the top-``wave_size`` leaves
by candidate gain (best-first, like the reference's leaf-wise ArgMax over
best_split_per_leaf_, serial_tree_learner.cpp:194), updates the per-row
``row_leaf`` vector with masked wheres (streaming, no gather/scatter), and
builds the wave's SMALLER children's histograms in one kernel pass — the
larger siblings come from the subtraction trick
(serial_tree_learner.cpp:311-320).  Total histogram passes per tree ≈
ceil((L-1)/25) + frontier ramp-up, independent of data size beyond the
pass cost itself.

Semantics vs the exact sequential leaf-wise order: identical while fewer
than ``num_leaves`` leaves exist and all wave candidates have positive
gain, EXCEPT that a wave commits its top-k splits before the children of
those splits can compete for the budget.  With ``wave_size=1`` the grower
reproduces the sequential order exactly (tests cross-check this).  Near
budget exhaustion (remaining budget < 2*wave_size) the **exact
device-side endgame** (``tpu_exact_endgame``, learner/endgame.py) takes
over on numeric non-EFB shapes: one batched kernel pass precomputes the
frontier candidates' smaller-child histograms and the remaining splits
are committed in the TRUE sequential best-first order by an on-device
while loop over the cached bank — typically zero further full-data
passes where the former wave-halving taper spent 3-4, and exact where
the taper was approximate.  Configurations outside the endgame gate keep
the taper; quality parity is asserted by tests on held-out loss.  The
``hist_passes`` field of the returned GrownTree counts full-data
histogram passes (root/mega + one per wave + one per endgame pass), and
``pass_log`` says what each of them was: its kind, the leaves it built,
the rows its kernels looped over, the lanes that carried a channel and the
compaction's blocks, written where the pass is counted from counts the
pass makes anyway (``log_pass``; ``TrainRecord`` rows carry it as
``passes``).

Forced splits (serial_tree_learner.cpp:450 ForceSplits) are applied as
pre-committed waves before gain-driven growth.  EFB, monotone
constraints, CEGB, categorical splits, interaction constraints, by-node
feature sampling, ExtraTrees random thresholds and quantized-gradient
histograms are fully supported (the latter four batched per wave with the
sequential node-id RNG streams, so wave_size=1 reproduces the partitioned
grower's sampling exactly).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..analysis.contracts import collective_contract, memory_budget
from ..models.tree import CAT_MASK, DEFAULT_LEFT_MASK, MISSING_NAN
from ..ops.histogram import build_histogram_leaves, histogram_subtract
from ..ops.histogram_pallas import DEFAULT_ROW_BLOCK, dense_pass_counts
from ..ops.quantize import (dequant_limbs, dequant_scales, hist_limbs,
                            quantize_wch)
from ..ops.split import (BIG, NEG_INF, _leaf_gain, best_split_per_feature,
                         best_split_two_bin,
                         leaf_output,
                         leaf_output_smoothed)
from .endgame import patch_child_pointers, write_split_records
from .serial import CommStrategy, GrownTree, local_best_candidate

__all__ = ["make_wave_grow_fn", "WAVE_SIZE", "Q_WAVE_SIZE",
           "lazy_bitmap_init", "LAZY_PACK", "wave_taper_k",
           "PASS_LOG_CAP", "PASS_FIRST", "PASS_WAVE", "PASS_ENDGAME"]

# The pass log (GrownTree.pass_log): one entry a counted pass, its ``kind``
# one of these, then ``leaves, rows, active_rows, blocks, blocks_active``.
PASS_FIRST, PASS_WAVE, PASS_ENDGAME = 0, 1, 2
# Entries a tree's log holds at most.  Every pass after the first commits
# a split, but for one barren wave that ends the tree and the forced waves:
# ``num_leaves + 1 + forced waves`` passes is the bound, and the log is
# that long where that is under this cap.  Beyond it the later passes'
# counts are summed into the last entry (``hist_passes`` says how many).
PASS_LOG_CAP = 64


def wave_taper_k(budget, W: int):
    """Endgame-taper wave width: commit min(W, budget) splits while the
    budget is ample, halve the wave once budget < 2W (with a W//4 floor
    capping the halving cascade) so freshly-created children get to
    compete near exhaustion.  Shared by the traced in-core wave body and
    the chunked streamed grower (ingest/grower.py), which must select
    identically for the streamed-vs-in-core bit-identity contract."""
    taper = jnp.maximum(budget // 2, jnp.minimum(W // 4, budget))
    return jnp.minimum(W, jnp.maximum(
        1, jnp.where(budget >= 2 * W, budget, taper)))

# Lazy-CEGB persistent bitmap layout: one bit per (feature, row), packed
# LSB-first into uint8 bytes — 8x less HBM than the former bool layout
# for wide lazy-penalized datasets.  The bool layout remains available
# behind ``lazy_bitpack=False`` (tests cross-check equality).
LAZY_PACK = 8


def lazy_bitmap_init(num_features: int, n_pad: int, bitpack: bool = True):
    """Fresh persistent 'feature computed for row' bitmap (the reference's
    feature_used_in_data_ bitset; allocated once per training run)."""
    if bitpack:
        return jnp.zeros((num_features, n_pad // LAZY_PACK), jnp.uint8)
    return jnp.zeros((num_features, n_pad), jnp.bool_)


def _pack_bits(m: jnp.ndarray) -> jnp.ndarray:
    """(N,) bool -> (N//8,) uint8, LSB-first."""
    b = m.reshape(-1, LAZY_PACK).astype(jnp.uint8)
    out = b[:, 0]
    for k in range(1, LAZY_PACK):
        out = out | (b[:, k] << k)
    return out


def _unpack_bits(p: jnp.ndarray) -> jnp.ndarray:
    """(..., N8) uint8 -> (..., N8*8) bool, LSB-first."""
    sh = jnp.arange(LAZY_PACK, dtype=jnp.uint8)
    bits = (p[..., None] >> sh) & jnp.uint8(1)
    return bits.reshape(*p.shape[:-1], -1).astype(jnp.bool_)

from ..ops.histogram_pallas import LEAF_CHANNELS as WAVE_SIZE  # 25/pass
from ..ops.histogram_pallas import Q_LEAF_CHANNELS as Q_WAVE_SIZE  # 42/pass


# ---------------------------------------------------------------------------
# Program contracts for the DP-wave collective sites (lint-trace enforced;
# site names match the WaveDPStrategy note_collective tallies so the
# contract, the telemetry tally and the collective call cannot drift).
# ---------------------------------------------------------------------------

# Histogram-MERGE sites per traced wave-tree program: the root pass, the
# wave-body pass inside the while loop (traced once), and the endgame
# bank pass.  tests/test_wave_scatter.py asserts this exact count on the
# scatter path.
WAVE_MERGE_SITES = 3


def _wave_merge_budget(ctx):
    """Merge collectives per traced tree: the three merge sites, plus —
    spec ramp on — ceil(log2 W) provisional-pass merges and the
    verification mega-pass (which replaces the root pass, hence the +1
    net; the budget tests/test_specramp.py counts on the jaxpr)."""
    import math
    if not ctx.get("spec_ramp"):
        return WAVE_MERGE_SITES
    w = max(2, int(ctx.get("wave_size", 2)))
    return WAVE_MERGE_SITES + math.ceil(math.log2(w)) + 1


def _hist_batch_bytes(ctx):
    """Full merged histogram batch: (W, F, B, 3) x itemsize."""
    return (int(ctx.get("wave_size", WAVE_SIZE)) * int(ctx["features"]) *
            int(ctx["bins"]) * 3 * int(ctx.get("itemsize", 4)))


def _hist_slice_bytes(ctx):
    """Feature-sliced reduce-scatter payload: each shard RECEIVES only
    its ceil(F/k) feature block of the merged batch — the 1/k budget
    the round-8 optimisation claims (PERF.md).  ``k`` is the mesh world
    size, so the same declaration checks W=4, W=8 and the trace-only
    W=64 pod mesh."""
    from ..analysis.contracts import world_size
    k = world_size(ctx)
    f_blk = -(-int(ctx["features"]) // k)
    return (int(ctx.get("wave_size", WAVE_SIZE)) * f_blk *
            int(ctx["bins"]) * 3 * int(ctx.get("itemsize", 4)))


def _exchange_payload_bytes(ctx):
    """O(W*k) winner exchange: per scan site a (W,) gain pmax, a (W,)
    feature pmin and one (W, 8) packed payload psum — never a histogram
    (the exchange_cap tests/test_wave_scatter.py bounds)."""
    w = int(ctx.get("wave_size", WAVE_SIZE))
    return 16 * max(2 * w, int(ctx.get("leaves", 2 * w))) * \
        int(ctx.get("itemsize", 4))


def _dcn_of(limit):
    """DCN ceiling derived from a per-op payload curve: the modeled
    cross-host share — dcn_fraction(ctx), (H-1)/H on a host-major axis —
    of that payload.  Declared explicitly per site so lint-trace bounds
    the pod (DCN) bytes separately from the per-op (ICI) bytes."""
    def dcn_bytes(ctx):
        from ..analysis.contracts import dcn_fraction
        return limit(ctx) * dcn_fraction(ctx)
    return dcn_bytes


collective_contract(
    "data_parallel/wave/hist_psum", "psum",
    max_count=_wave_merge_budget, max_bytes_per_op=_hist_batch_bytes,
    max_dcn_bytes_per_op=_dcn_of(_hist_batch_bytes),
    note="one full-batch histogram psum per merge site")
collective_contract(
    "data_parallel/wave/hist_reduce_scatter", "psum_scatter",
    max_count=_wave_merge_budget, max_bytes_per_op=_hist_slice_bytes,
    max_dcn_bytes_per_op=_dcn_of(_hist_slice_bytes),
    note="one reduce_scatter per merge site, 1/k received payload")
collective_contract(
    "data_parallel/wave/winner_exchange", ("pmax", "pmin", "psum"),
    max_count=lambda ctx: 3 * _wave_merge_budget(ctx),
    max_bytes_per_op=_exchange_payload_bytes,
    max_dcn_bytes_per_op=_dcn_of(_exchange_payload_bytes),
    note="pmax/pmin/psum triple per candidate-scan site, O(W*k) bytes")
collective_contract(
    "data_parallel/wave/scalar_sum", "psum",
    max_count=8, max_bytes_per_op=_exchange_payload_bytes,
    max_dcn_bytes_per_op=_dcn_of(_exchange_payload_bytes),
    note="leaf totals / root sums — small vectors only")
collective_contract(
    "data_parallel/wave/quant_scale", "pmax",
    max_count=2, max_bytes_per_op=8, max_dcn_bytes_per_op=8,
    note="global gradient/hessian quantization scales (two scalars)")


# ---------------------------------------------------------------------------
# Memory budget for the wave grower program family (lint-mem enforced).
# The footprint is histogram-channel dominated: the per-leaf bank
# (L,F,B,3), the kernel's channel batch (the quantized kernel always
# builds Q_WAVE_SIZE=42 channels, the f32 one 2*wave trial channels) and
# the wave loop's subtraction/scan temporaries — measured ~5 channel
# layers of working set per batch layer at the lint geometry; the curve
# budgets 6 for headroom.  Row arrays: bins (F,N) uint8, held twice
# while a tree grows (as the histogram kernels stream them and as the
# view the row-update kernel fetches its columns from) + grad/hess/
# mask/row_leaf/quantized lanes, ~24 B/row beyond the bin matrix.
# ---------------------------------------------------------------------------

def wave_grow_hbm_bytes(ctx):
    """Per-device HBM curve of one wave-grower tree program, as a
    function of (rows, features, bins, wave_size, leaves, world_size) —
    the statically answerable half of "will 10^8 rows fit at W=64?"."""
    from ..analysis.contracts import world_size
    f = int(ctx["features"])
    b = int(ctx["bins"])
    it = int(ctx.get("itemsize", 4))
    r = -(-int(ctx["rows"]) // world_size(ctx))
    wave = int(ctx.get("wave_size", WAVE_SIZE))
    kernel_ch = Q_WAVE_SIZE if ctx.get("quantized") else WAVE_SIZE
    layers = int(ctx.get("leaves", 2)) + 6 * max(2 * wave, kernel_ch)
    hist = layers * f * b * 3 * it
    rows = r * (2 * f + 24)
    return hist + rows + (1 << 20)


memory_budget(
    "wave/grow", ("serial", "wave"), wave_grow_hbm_bytes,
    note="per-leaf bank + 6 channel layers of wave batches + row arrays")


def make_wave_grow_fn(*, num_leaves: int, num_features: int, max_bins: int,
                      max_depth: int, split_params, hist_impl: str,
                      any_cat: bool = True, interpret: bool = None,
                      pack4: bool = False, pipeline: str = None,
                      jit: bool = True, wave_size: int = 0,
                      efb_dims=None, efb_layout: tuple = (),
                      feature_contri: tuple = (),
                      strategy=None, quantized: bool = False,
                      gq_max: int = 127, hq_max: int = 127,
                      renew_leaf: bool = False, stochastic: bool = True,
                      interaction_groups: tuple = (),
                      cegb_lazy: tuple = (), spec_ramp: bool = False,
                      spec_tol: float = 0.3,
                      spec_subsample: int = 1 << 19,
                      forced_splits: tuple = (),
                      mc_inter: bool = False,
                      exact_endgame: bool = True,
                      lazy_bitpack: bool = True,
                      sampled: bool = False,
                      hist_acc_rows: int = 0):
    """Build the wave single-tree grower.

    Returned signature matches the partitioned grower:
    ``grow(X_T, grad, hess, bag_mask, num_bins, is_cat, has_nan, monotone,
    cegb_penalty, efb_arrays, feature_mask) -> GrownTree`` with X_T the
    FEATURE-MAJOR (G, N) bin matrix (bundle-space under EFB), N a multiple
    of the Pallas row block when hist_impl == 'pallas'.

    ``efb_dims`` / ``efb_layout``: (G, Bb) of an EFB-bundled data set and
    its static layout (efb.py ``BundleInfo.layout``): the histograms are
    built and banked in bundle space, the scans read member features out
    of them by static slices (``make_scan_expand``, scope
    ``lgbm.wave.efb_expand``), and the fused row update takes a bundled
    slot's left set in bundle codes.

    ``strategy`` hooks the data-parallel mesh in: under shard_map with
    row-sharded X_T/grad/hess, each wave's (W, G, Bb, 3) histogram batch
    is merged with ONE collective (instead of the per-split
    reduce-scatter of the sequential DP learner,
    data_parallel_tree_learner.cpp:155-173's pattern amortized over up
    to 25 splits), in one of two modes:

    * ``strategy.reduce_hist`` (psum) — every shard holds the full
      merged batch and the candidate scans run replicated with no
      further communication;
    * ``strategy.hist_scatter`` — ``reduce_hist_scatter`` psum_scatters
      the batch over a padded feature-block axis: each shard keeps only
      its G/k block, scans that slice (per-feature operands sliced to
      match), and an O(W*k) winner exchange (``exchange_collectives``)
      recombines the block-local bests into the global per-leaf winners
      — 1/k the wire residency and scan FLOPs, identical results.

    ``sampled``: the booster this grower is built for can hand it a
    ``bag_mask`` with zeros (GOSS, bagging, a masked CV fold).  Every
    histogram pass of the Pallas route then leaves the out-of-bag rows
    out of its channels and contracts the in-bag rows alone
    (``leaf_hists``; the ramp's passes on its subsample too); the tree
    and every row's leaf are the same either way.  A booster that never
    samples keeps the dense first pass.

    ``hist_acc_rows`` (quantized): 0, or the most rows a histogram pass
    may add into one int32 on this data set (ops/quantize.py: rows a
    shard x the fullest bin's share x 127 can pass 2^31).  The kernels
    then sum a pass in segments and every integer histogram of the grower
    carries five lanes a bin, [g_lo, h_lo, count, g_hi, h_hi]: limbs add,
    subtract and cross the mesh as the three sums do; ``dq`` alone puts
    them together.
    """
    L = num_leaves
    F = num_features
    ch_cap = Q_WAVE_SIZE if quantized else WAVE_SIZE
    W = max(1, min(int(wave_size) or ch_cap, ch_cap, L - 1))
    use_efb = efb_dims is not None
    G, Bb = efb_dims if use_efb else (F, max_bins)
    pallas = hist_impl == "pallas"
    wide = bool(quantized and hist_acc_rows)
    HC = 5 if wide else 3      # int32 lanes a bin of an integer histogram
    if pallas:
        from ..ops.histogram_pallas import (
            build_histogram_pallas, build_histogram_pallas_leaves,
            bin_rows_view, build_histogram_pallas_leaves_q8,
            gather_bin_rows, pack_weights8, unpack_bins4,
            wave_row_update_pallas, wave_trial_channels_pallas)
    if pack4 and not pallas:
        raise ValueError("pack4 bins require hist_impl='pallas'")
    if pack4 and (efb_dims is not None or max_bins > 16 or any_cat):
        raise ValueError("pack4 bins require numeric non-EFB data with "
                         "max_bins <= 16")

    sp = split_params
    use_mc = split_params.use_monotone
    use_sm = split_params.path_smooth > 0.0
    # per-node feature sampling / random thresholds / interaction
    # constraints, traced per wave (the partitioned grower's node_mask /
    # node_rand / allowed_features, learner/partitioned.py:96-128, batched
    # over the wave's 2W children).  Node ids mirror the sequential
    # numbering (2t, 2t+1 for node t's children; 2L for the root) so
    # wave_size=1 reproduces the partitioned grower's streams exactly.
    use_bynode = sp.feature_fraction_bynode < 1.0
    use_et = sp.extra_trees
    use_ic = len(interaction_groups) > 0
    # CEGB lazy feature costs (cost_effective_gradient_boosting.hpp
    # CalculateOndemandCosts): penalty[f] per row in the candidate leaf
    # whose feature f has not yet been computed (used by any split on the
    # row's path).  The wave grower keeps rows in original order, so the
    # per-(feature, child) unused counts are small matvecs against the
    # (F, N) used bitmap.  ``cegb_lazy`` arrives pre-scaled by
    # cegb_tradeoff (like the coupled penalties).
    use_lazy = len(cegb_lazy) > 0
    if use_lazy:
        lazy_pen = jnp.asarray(cegb_lazy, jnp.float32)       # (F,)
    # Speculative ramp eligibility (all static).  The frontier ramp
    # (1 -> 2 -> 4 -> ... leaves) costs ~log2(W) full-data histogram
    # passes with most lanes idle; when eligible, grow() instead grows a
    # provisional <=W-leaf subtree on a row subsample, verifies it with
    # ONE full-data W-channel pass, and commits every provisional split
    # whose EXACT full-data gain is within ``spec_tol`` of that node's
    # exact best split.  Exactness: committed gains/sums/hists all come
    # from the full-data channel sums — the subsample only chooses which
    # histograms to precompute; a bad guess costs a skipped commit, never
    # a wrong number.  Gated to the Pallas numeric path (the shapes the
    # flagship benchmark runs) — SERIAL or row-sharded DATA-PARALLEL: a
    # WaveDPStrategy advertises ``spec_ok`` and the provisional subsample
    # waves psum their histograms over ICI exactly like committed waves
    # (one collective per provisional pass), so every shard grows the
    # same provisional tree and verifies it against the full sharded
    # data.  Every other configuration keeps the plain ramp.
    spec_dp_ok = strategy is None or getattr(strategy, "spec_ok", False)
    spec_shards = int(getattr(strategy, "nshards", 1) or 1)
    use_spec = (spec_ramp and hist_impl == "pallas" and not any_cat and
                not use_efb and max_bins <= 255 and not use_mc and
                not use_sm and not use_ic and not use_bynode and
                not use_et and not use_lazy and not sp.use_cegb and
                spec_dp_ok and max_depth <= 0 and
                not feature_contri and W >= 2 and L >= 3 * W and
                not forced_splits)
    # Narrow-dtype fast path (shared by the row updates and the endgame):
    # bin codes stay uint8 (255 reserved as the no-NaN sentinel) and leaf
    # ids uint8 when the tree fits — 4x less HBM traffic than int32.
    small_bins = (not use_efb) and max_bins <= 255
    # The fused row-update kernel reads uint8 codes with 255 free: feature
    # bins, or the codes of bundle columns (a bundle holds at most 255)
    fused_update = pallas and max(max_bins, Bb) <= 255
    # Exact device-side endgame eligibility (all static).  Once the
    # remaining budget drops below 2W the halving taper is replaced by
    # ONE batched kernel pass over the frontier candidates' smaller
    # children plus a true sequential best-first selection over the
    # cached histogram bank (learner/endgame.py docnotes).  Gated off the
    # per-wave-stateful features (monotone bounds, interaction paths,
    # per-node RNG streams, lazy-CEGB bitmap upkeep) and categorical/EFB
    # shapes; works on the serial AND row-sharded DP paths (the batched
    # pass rides the same one-psum-per-pass reduction as committed
    # waves), quantized or exact, any hist impl.
    use_endgame = (exact_endgame and not any_cat and not use_efb and
                   small_bins and not use_mc and not use_ic and
                   not use_bynode and not use_et and not use_lazy and
                   L > 2)
    # Forced splits (serial_tree_learner.cpp:450 ForceSplits): the
    # BFS-ordered (leaf, inner feature, threshold bin) triples are applied
    # as PRE-COMMITTED waves before gain-driven growth — statically
    # grouped so no wave splits a leaf created (or already split) in the
    # same wave, which keeps the sequential right-child numbering
    # identical to the triples' BFS next_id assignment.  Child sums come
    # from the parent's pooled histogram, so forced waves reuse the exact
    # per-wave machinery (row update, one kernel pass, subtraction,
    # children scans) with only split SELECTION overridden.
    forced_waves: list = []
    if forced_splits:
        nf = min(len(forced_splits), L - 1)
        cur: list = []
        blocked: set = set()
        nl_sim = 1
        for (leaf_, f_, b_) in forced_splits[:nf]:
            if leaf_ in blocked or len(cur) == W:
                forced_waves.append(cur)
                cur, blocked = [], set()
            cur.append((leaf_, f_, b_))
            blocked.add(leaf_)     # split once per wave
            blocked.add(nl_sim)    # fresh right child: next wave only
            nl_sim += 1
        if cur:
            forced_waves.append(cur)
    PL = min(L + 1 + len(forced_waves), PASS_LOG_CAP)   # the pass log's length
    # Feature-sliced reduce-scatter histogram merge (all static): under a
    # row-sharded WaveDPStrategy with ``hist_scatter``, each wave's
    # (W, G, Bb, 3) batch is psum_scatter'd over a padded feature-block
    # axis — every shard materializes only its G/k slice of the merged
    # histogram, runs the candidate scan on that slice, and an O(W*k)
    # winner exchange (pmax gain / pmin global feature / psum'd payload)
    # picks the global best split per frontier leaf.  This is the
    # reference DP learner's ReduceScatter refinement
    # (data_parallel_tree_learner.cpp:155-173, network.h:164) amortized
    # over the wave's channels: 1/k the ICI residency of the full-batch
    # psum and 1/k the scan FLOPs, with bit-identical results (the
    # scattered block equals the same slice of the psum'd batch).  Gated
    # off categorical shapes (the sorted-subset search's static cat_idx
    # positions index full feature space), EFB (bundle->feature expansion
    # needs the whole bundle axis), forced splits (child sums are read
    # from the parent's pooled histogram at an arbitrary global feature)
    # and lazy CEGB (its per-(feature, child) unused counts would add a
    # full-F psum per wave) — those configs keep the full-batch psum.
    k_sc = int(getattr(strategy, "nshards", 1) or 1)
    use_scatter = (bool(getattr(strategy, "hist_scatter", False)) and
                   k_sc > 1 and not any_cat and not use_efb and
                   not use_lazy and not forced_waves)
    if use_scatter:
        FP_SC = -(-G // k_sc) * k_sc   # feature axis padded to k blocks
        FB_SC = FP_SC // k_sc          # features owned per shard
        F_PAD_SC = FP_SC - G
    # PV-Tree voting histogram merge (arXiv:1611.01276) on the wave batch
    # (all static): under a row-sharded strategy with ``hist_voting``, the
    # per-leaf histogram POOL stays shard-LOCAL (so the subtraction trick
    # still holds shard-by-shard) and only the voted top-2k features'
    # slices of each scan batch are psum'd — per-leaf cross-shard wire
    # volume drops from F*B to 2k*B.  Quantized batches merge as exact
    # int32 and dequantize after the psum, so at 2k >= F the voted path
    # is bit-identical to the full-batch DP merge.  Gated off the same
    # shapes as scatter (cats / EFB / lazy CEGB / forced splits need
    # full-feature merged histograms); those configs fall back to the
    # strategy's full reduce_hist.  Mutually exclusive with scatter: a
    # strategy declares one merge mode.
    use_voting = (bool(getattr(strategy, "hist_voting", False)) and
                  k_sc > 1 and not use_scatter and not any_cat and
                  not use_efb and not use_lazy and not forced_waves)
    if use_voting:
        TOPK_V = max(1, min(int(getattr(strategy, "top_k", 10)), F))
        SEL_V = min(2 * TOPK_V, F)     # voted features aggregated per leaf
    G_loc = FB_SC if use_scatter else G   # this shard's histogram width
    if use_bynode:
        import math as _math
        kcnt = max(1, int(_math.ceil(F * sp.feature_fraction_bynode)))
    if use_ic:
        import numpy as _np
        _g = _np.zeros((len(interaction_groups), F), bool)
        for gi, feats in enumerate(interaction_groups):
            for ff in feats:
                if 0 <= ff < F:
                    _g[gi, ff] = True
        ic_groups = jnp.asarray(_g)

        def allowed_features(path):
            """Union of constraint sets containing every feature already
            used on the branch (col_sampler.hpp GetByNode)."""
            compat = jnp.logical_not(
                jnp.any(path[None, :] & jnp.logical_not(ic_groups), axis=1))
            return jnp.any(ic_groups & compat[:, None], axis=0)

    def _child_out(g, h, cnt, parent_out):
        if use_sm:
            return leaf_output_smoothed(g, h, cnt, parent_out, sp)
        return leaf_output(g, h, sp)

    def grow(X_T: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
             bag_mask: jnp.ndarray, num_bins: jnp.ndarray,
             is_cat: jnp.ndarray, has_nan: jnp.ndarray,
             monotone: jnp.ndarray, cegb_penalty: jnp.ndarray,
             efb_arrays: tuple, feature_mask: jnp.ndarray,
             quant_key: jnp.ndarray = None,
             node_key: jnp.ndarray = None,
             lazy_used: jnp.ndarray = None):
        # Under ``pack4`` X_T is the nibble-packed (G, N//2) byte matrix
        # (ops/histogram_pallas.pack_bins4): the histogram kernels
        # consume it directly (half the streamed bin bytes) and the row
        # updates gather their W winning features' packed columns and
        # unpack them on the fly.  Every other shape the fused row-update
        # kernel takes hands it a feature-major view of the WHOLE matrix
        # and the W feature ids: the kernel fetches the columns it needs
        # itself, and no (W, N) array is built between X_T and it.
        n = X_T.shape[1] * 2 if pack4 else X_T.shape[1]
        # what the pass log counts looped rows in (exact in int32 where a
        # tree's rows are not)
        row_unit = math.gcd(n, DEFAULT_ROW_BLOCK)

        def log_pass(s, kind, leaves, counts):
            """``s["pass_log"]`` with the pass that ``s["hist_passes"]``
            is about to count written at that index: its ``kind``, the
            ``leaves`` whose histograms it built and ``hist_waves``'
            ``counts``.  Past the log's length the counts are summed
            into the last entry, which keeps the newest kind."""
            i = jnp.minimum(s["hist_passes"], PL - 1)
            entry = jnp.concatenate(
                [jnp.reshape(leaves, (1,)).astype(jnp.int32), counts])
            return s["pass_log"].at[i, 1:].add(entry).at[i, 0].set(kind)

        def first_pass(leaves, counts):
            """The two counters of a state whose first pass is done."""
            zero = {"hist_passes": jnp.asarray(0, jnp.int32),
                    "pass_log": jnp.zeros((PL, 6), jnp.int32)}
            return {"hist_passes": jnp.asarray(1, jnp.int32),
                    "pass_log": log_pass(zero, PASS_FIRST, leaves, counts)}

        def router_bins(mat):
            """What the fused row-update kernel reads ``mat``'s columns
            from: made once per tree (a relayout of ``mat`` on a TPU)."""
            if pack4 or not fused_update:
                return mat
            return bin_rows_view(mat, pipeline)

        def router_args(bins, feats):
            """``(bins, feats=)`` of the fused row-update kernel's two
            entries for the rows of ``bins`` (a :func:`router_bins`)."""
            if pack4:
                return unpack_bins4(gather_bin_rows(bins, feats)), None
            return bins, feats

        def route_rows(bins, feats, rl, tab, cat=None):
            bins, feats = router_args(bins, feats)
            return wave_row_update_pallas(
                bins, rl, tab, feats=feats, cat=cat, bundled=use_efb,
                interpret=interpret, pipeline=pipeline)

        with jax.named_scope("lgbm.wave.row_update"):
            X_R = router_bins(X_T)
        if strategy is not None:
            # shallow per-trace copy: traced array attributes must not
            # outlive the trace on the learner's long-lived strategy object
            import copy
            strat = copy.copy(strategy)
            strat.num_bins_full = num_bins
            strat.is_cat_full = is_cat
            strat.has_nan_full = has_nan
            strat.monotone_full = monotone
        else:
            strat = CommStrategy(num_bins, is_cat, has_nan, monotone)
        strat.cegb_full = cegb_penalty if sp.use_cegb else None
        if feature_contri:
            strat.contri_full = jnp.asarray(feature_contri, jnp.float32)
        nb_full, ic_full, hn_full = num_bins, is_cat, has_nan

        if use_scatter:
            # this shard's feature block [f_start, f_start + FB_SC): the
            # scan sees sliced per-feature descriptors; winner feature
            # indices are remapped to global space in the exchange
            f_start = (jax.lax.axis_index(strat.axis_name) *
                       FB_SC).astype(jnp.int32)

            def _slf(a, fill):
                """(F,) per-feature array -> this shard's (FB_SC,) block
                (padded features get inert ``fill`` values)."""
                if F_PAD_SC:
                    a = jnp.concatenate(
                        [a, jnp.full((F_PAD_SC,), fill, a.dtype)])
                return jax.lax.dynamic_slice_in_dim(a, f_start, FB_SC, 0)

            def _slf2(a, fill):
                """(..., F) batch -> (..., FB_SC) block slice."""
                if F_PAD_SC:
                    a = jnp.concatenate(
                        [a, jnp.full(a.shape[:-1] + (F_PAD_SC,), fill,
                                     a.dtype)], axis=-1)
                return jax.lax.dynamic_slice_in_dim(a, f_start, FB_SC,
                                                    a.ndim - 1)

            nb_sc = _slf(nb_full, 1)      # 1-bin pads: never splittable
            ic_sc = _slf(ic_full, False)
            hn_sc = _slf(hn_full, False)
            mono_sc = _slf(monotone, 0)
            xmax_sc, xmin_sc, xsum_sc = strat.exchange_collectives()

            def _exchange(cands):
                """Combine per-shard block-local best candidates into the
                global per-leaf winners: pmax of the gain, pmin of the
                global feature index among gain-achieving blocks (the
                same lowest-feature tie-break a full-space argmax
                applies), then one psum of the winner's packed payload
                (bin, default_left, left/right sums) — O(k) floats per
                leaf, the SplitInfo allreduce-max analog.  ``member``
                stays block-local: categorical shapes never take the
                scatter path, so it is identically all-False."""
                g, f_loc, b, dl, ls, rs, member = cands
                gmax = xmax_sc(g)
                f_glob = f_start + f_loc
                cf = jnp.where(g >= gmax, f_glob, jnp.int32(2 ** 30))
                f_win = xmin_sc(cf)
                is_win = (f_glob == f_win) & (g >= gmax)
                pack = jnp.concatenate([
                    b.astype(jnp.float32)[:, None],
                    dl.astype(jnp.float32)[:, None], ls, rs], axis=-1)
                pk = xsum_sc(jnp.where(is_win[:, None], pack, 0.0))
                return (gmax, f_win, pk[:, 0].astype(jnp.int32),
                        pk[:, 1] > 0, pk[:, 2:5], pk[:, 5:8], member)

        from ..efb import (bundle_left_sets, make_bundle_decode,
                           make_expand_hist, make_scan_expand)
        # the per-leaf (F, B) gather: forced splits only (and the ramp's
        # node scan, which EFB switches off); the scans take scan_expand
        expand_hist = make_expand_hist(efb_arrays if use_efb else (),
                                       F, G, Bb)
        bundle_decode = make_bundle_decode(efb_arrays if use_efb else ())
        f_bundle = efb_arrays[1] if use_efb else None
        if use_efb:
            scan_expand = make_scan_expand(efb_layout, G, Bb, max_bins)

        with jax.named_scope("lgbm.quantize"):
            gm = (grad * bag_mask).astype(jnp.float32)
            hm = (hess * bag_mask).astype(jnp.float32)
            in_bag = bag_mask > 0
            cnt_mask = in_bag.astype(jnp.float32)
        if use_lazy:
            # packed vs bool layout of the persistent `used` bitmap: follow
            # whatever the learner threads in (its dtype is static at trace
            # time); fresh bitmaps pack only when the row count allows it
            lp = (lazy_used.dtype == jnp.uint8) if lazy_used is not None \
                else (lazy_bitpack and n % LAZY_PACK == 0)
        if pallas:
            if not quantized:
                with jax.named_scope("lgbm.quantize"):
                    w8 = pack_weights8(grad, hess, bag_mask)
            bins_rows = None
        else:
            # row-major copy made ONCE per grow call (outside the wave
            # loop; XLA cannot hoist it out of lax.while itself)
            bins_rows = jnp.swapaxes(X_T, 0, 1)

        if quantized:
            # per-tree linear quantization scales from cross-shard maxima
            # (gradient_discretizer.cpp DiscretizeGradients); every DP
            # shard derives the same scales, so integer histograms psum
            # exactly.
            with jax.named_scope("lgbm.quantize"):
                gmax = strat.reduce_max(jnp.max(jnp.abs(gm)))
                hmax = strat.reduce_max(jnp.max(hm))
                g_scale = jnp.maximum(gmax, jnp.float32(1e-30)) / gq_max
                h_scale = jnp.maximum(hmax, jnp.float32(1e-30)) / hq_max
                qscales = dequant_scales(g_scale, h_scale)
                qk = quant_key if quant_key is not None else \
                    jax.random.PRNGKey(0)
                wch0 = quantize_wch(grad, hess, bag_mask, g_scale, h_scale,
                                    strat.shard_key(qk), gq_max=gq_max,
                                    hq_max=hq_max, stochastic=stochastic)

            def dq(h):
                """int32 channel sums -> f32 (sum_grad, sum_hess, count)."""
                if wide:
                    return dequant_limbs(h) * qscales
                return h.astype(jnp.float32) * qscales

        _dqh = dq if quantized else (lambda h: h)

        def _scan_hists(h, totals):
            """The histogram form the candidate scans consume: the
            dequantized (and, under EFB, feature-expanded) batch
            normally; under voting the RAW shard-local batch — the
            voted merge inside many_candidates dequantizes AFTER its
            exact integer psum of the selected slices."""
            if use_voting:
                return h
            if use_efb:
                # (wide, narrow): member features where they lie in bundle
                # space (efb.make_scan_expand), for _efb_candidates
                with jax.named_scope("lgbm.wave.efb_expand"):
                    return jax.vmap(scan_expand.expand)(_dqh(h), totals)
            return jax.vmap(expand_hist)(_dqh(h), totals)

        def _reduce_waves(h, k, with_totals=False):
            """Merge a freshly built (c, G, Bb, 3) histogram batch across
            row shards, trimmed to the first ``k`` channels.  Scatter
            mode pads the feature axis to the block quantum and
            reduce-scatters it, so this shard keeps only its fully
            reduced (k, FB_SC, Bb, 3) block.  ``with_totals``
            additionally returns the (k, 3) per-channel leaf totals:
            under scatter they come from a tiny psum of the LOCAL
            pre-merge batch's feature-0 bin sums (each shard's slice
            holds a different feature, whose f32 bin sums agree only up
            to rounding — and pure-pad shards hold no real feature at
            all); otherwise from the merged batch.  Quantized batches
            stay int32 end to end and dequantize AFTER the exact integer
            sum, so totals are identical across shards and across merge
            modes.  Voting mode returns the batch UNMERGED (shard-local):
            the vote-and-psum of the winning feature slices happens
            inside many_candidates; only the (k, 3) leaf totals cross
            the wire here."""
            hk = h[:k]
            if use_voting:
                if not with_totals:
                    return hk
                return hk, _dqh(strat.reduce_sum(hk[:, 0].sum(axis=1)))
            if use_scatter:
                hp = jnp.pad(hk, ((0, 0), (0, F_PAD_SC), (0, 0), (0, 0))) \
                    if F_PAD_SC else hk
                hmg = strat.reduce_hist_scatter(hp)
                if not with_totals:
                    return hmg
                return hmg, _dqh(strat.reduce_sum(hk[:, 0].sum(axis=1)))
            hmg = strat.reduce_hist(hk)
            if not with_totals:
                return hmg
            return hmg, _dqh(hmg[:, 0].sum(axis=1))

        def leaf_hists(bins, w, ch, bag, sparse):
            """``(histograms, counts)`` of one call of the Pallas leaf
            kernel on ``bins`` / ``w`` (``counts``: ``hist_waves``; the tree's or the
            ramp's subsample's).  THE place where a grower built for a
            booster that samples rows leaves the out-of-bag ones out:
            their weight levels are all 0, so with channel -1 and the
            compacting entry the sums are the same without their zero
            terms, over the rows of ``bag`` alone."""
            if sampled:
                ch, sparse = jnp.where(bag, ch, -1), True
            build = build_histogram_pallas_leaves_q8 if quantized \
                else build_histogram_pallas_leaves
            h = build(bins, w, ch, num_bins=Bb, interpret=interpret,
                      pipeline=pipeline, bins_packed=pack4, compact=sparse,
                      **({"acc_rows": hist_acc_rows} if wide else {}))
            return h if sparse else (h, dense_pass_counts(w.shape[1]))

        def hist_waves(ch, k=W, with_totals=False, sparse=False):
            """(k, G_loc, Bb, 3) histograms of the wave's leaf channels,
            reduced across row shards (serial: identity; DP scatter mode:
            this shard's feature block of the merged batch).  ``k`` trims
            the cross-shard reduction to the channels actually used (the
            root pass needs only channel 0).  Quantized mode returns
            exact int32 channel sums (dequantize with ``dq``).

            ``sparse`` is a property of the call site, not an option: the
            wave body and the endgame pass channels that hold the
            splits' SMALLER children and -1 for every other row, so
            their Pallas ``dma`` kernels first move the active rows to
            the front and contract only the row blocks that hold them
            (ops/histogram_pallas.py ``compact``; each shard compacts its
            own rows, before the collective).  The verify pass of the
            ramp and the root pass put every row in a channel: they keep
            the direct call, unless the grower is built for a booster
            that samples rows: there every pass is sparse
            (``leaf_hists``).  Returns ``(histograms, counts)``, ``counts``
            this shard's ``[rows, active_rows, blocks, blocks_active]``
            (ops/histogram_pallas.py ``dense_pass_counts``): the rows the
            kernel looped over, in ``row_unit``s, the lanes that carry a
            channel, the compaction blocks and those that hold such a
            lane.  A pass that puts every row in a channel says so
            itself; no pass over ``ch`` is added for the log."""
            if pallas:
                h, counts = leaf_hists(X_T, wch0 if quantized else w8, ch,
                                       in_bag, sparse)
            elif quantized:
                # off-TPU emulation: f32 sums of integer levels are
                # exact while |sum| < 2^24 per bin — ample for the
                # CPU/test shards this path serves (the Pallas path
                # accumulates true int32 and has no such cap)
                h = build_histogram_leaves(
                    bins_rows, wch0[0].astype(jnp.float32),
                    wch0[1].astype(jnp.float32),
                    wch0[2].astype(jnp.float32), ch,
                    num_channels=W, num_bins=Bb, impl=hist_impl)
                h = jnp.round(h).astype(jnp.int32)
                if wide:
                    h = hist_limbs([h])
            else:
                h = build_histogram_leaves(
                    bins_rows, gm, hm, cnt_mask, ch,
                    num_channels=W, num_bins=Bb, impl=hist_impl)
            if not pallas:
                counts = dense_pass_counts(n, ch if sparse else None)
            return (_reduce_waves(h, k, with_totals),
                    counts.at[0].set(counts[0] // row_unit))

        def feature_col(feat):
            """FEATURE-space bin codes (N,) of one feature (decoded from
            its bundle column under EFB; efb.make_bundle_decode)."""
            g = f_bundle[feat] if use_efb else feat
            if pack4:
                return unpack_bins4(
                    jax.lax.dynamic_slice(X_T, (g, 0), (1, n // 2)))[0]
            v = jax.lax.dynamic_slice(X_T, (g, 0), (1, n))[0]
            if small_bins:
                return v                                     # uint8
            return bundle_decode(v.astype(jnp.int32), feat)

        def _efb_candidates(hists, sums, bounds, depths, pouts, fms, rbs,
                            cegb2, cegb, contri):
            """Best-split candidates for k leaves of a bundled data set.
            ``hists`` is ``_scan_hists``' (wide (k, Fw, B, 3), narrow
            (k, 2, 3, Fn)) pair: the wide class takes the scan every
            unbundled feature takes, the two-bin members of bundles the
            one-split scan with the features on the lanes (ops/split.py
            ``best_split_two_bin``).  A class's operands are cut out of
            the (F,) ones by its static feature ids; the winner is the
            best gain, the LOWEST feature id among equal gains, as the
            argmax over an (F,) gain vector picks it."""
            hw, hn_ = hists
            wid, nid = scan_expand.wide_ids, scan_expand.narrow_ids
            per_node = use_ic or use_bynode    # else fms is feature_mask's
            pen = cegb2 if cegb2 is not None else cegb
            pen_k = cegb2 is not None

            def cut(a, ids):
                return None if a is None else jnp.take(a, ids, axis=-1)

            def pick(gain, ids):
                """(best gain, its feature id, its place) of one class."""
                gmax = jnp.max(gain)
                fid = jnp.min(jnp.where(gain >= gmax, ids, jnp.int32(2 ** 30)))
                return gmax, fid, jnp.argmax(ids == fid)

            def wide_one(h, s, bd, d, po, fm, pn, rb):
                fs = best_split_per_feature(
                    h, s, nb_w, ic_w, hn_w, sp_w, mono_w,
                    bd if use_mc else None, d, pn, contri_w, po, rb)
                g, fid, at = pick(jnp.where(fm, fs.gain, NEG_INF), wid_j)
                return (g, fid, fs.threshold_bin[at], fs.default_left[at],
                        fs.left_sum[at], fs.right_sum[at], fs.cat_member[at])

            def narrow_one(h2, s, bd, d, po, fm, pn):
                gain = best_split_two_bin(
                    h2[0], s, sp, mono_n, bd if use_mc else None, d, pn,
                    contri_n, po)
                g, fid, at = pick(jnp.where(fm, gain, NEG_INF), nid_j)
                ls = h2[0][:, at]
                return (g, fid, jnp.int32(0), jnp.asarray(False), ls, s - ls,
                        jnp.zeros((max_bins,), jnp.bool_))

            def operands(ids):
                fm = cut(fms if per_node else feature_mask, ids)
                return (fm, 0 if per_node else None,
                        cut(pen, ids), 0 if pen_k else None)

            outs = []
            if len(wid):
                wid_j = jnp.asarray(wid)
                nb_w, ic_w, hn_w = nb_full[wid_j], ic_full[wid_j], hn_full[wid_j]
                mono_w = monotone[wid_j]
                contri_w = cut(contri, wid_j)
                place = {int(f): i for i, f in enumerate(wid)}
                sp_w = sp._replace(cat_idx=tuple(place[c] for c in sp.cat_idx))
                fm, fm_ax, pn, pn_ax = operands(wid_j)
                rb = cut(rbs, wid_j)
                outs.append(jax.vmap(
                    wide_one, in_axes=(0, 0, 0, 0, 0, fm_ax, pn_ax,
                                       None if rb is None else 0))(
                    hw, sums, bounds, depths, pouts, fm, pn, rb))
            if len(nid):
                nid_j = jnp.asarray(nid)
                mono_n = monotone[nid_j]
                contri_n = cut(contri, nid_j)
                fm, fm_ax, pn, pn_ax = operands(nid_j)
                outs.append(jax.vmap(
                    narrow_one, in_axes=(0, 0, 0, 0, 0, fm_ax, pn_ax))(
                    hn_, sums, bounds, depths, pouts, fm, pn))
            if len(outs) == 1:
                return outs[0]
            a, b = outs
            take_b = (b[0] > a[0]) | ((b[0] == a[0]) & (b[1] < a[1]))
            return tuple(
                jnp.where(take_b.reshape((-1,) + (1,) * (x.ndim - 1)), y, x)
                for x, y in zip(a, b))

        def _voting_candidates(hists, sums, bounds, depths, pouts, fms,
                               rbs, cegb2, cegb, contri):
            """PV-Tree voted merge + scan for k leaves (the voting
            counterpart of the scatter exchange).  ``hists`` arrive RAW
            and shard-LOCAL (int32 under quantized): each shard scores
            its local batch with the 1/num_machines-relaxed constraints
            (voting_parallel_tree_learner.cpp:62-63), votes its top-k
            features per leaf, the votes ride one small all_gather, and
            only the global top-2k features' histogram slices are
            psum'd — (k, 2k, B, 3) on the wire instead of (k, F, B, 3).
            The final scan runs on the merged slices with the FULL
            split params and global leaf sums; the winner's slice-local
            feature index maps back through ``selected``.  Every shard
            computes identical votes and identical merged slices, so
            candidates are replicated without any exchange — and with
            2k >= F, ``selected`` (sorted ascending) is the identity
            permutation and the scan is bit-identical to the full-batch
            DP merge."""
            kl = hists.shape[0]
            # 1. local candidate gains, relaxed constraints, local view
            #    (the local leaf totals are exact: any feature's bins sum
            #    to the shard's total — EFB is gated out under voting)
            lp_v = getattr(strat, "local_params", None) or sp
            lsum_loc = _dqh(hists[:, 0].sum(axis=1))

            def one_local(h, s, bd, d, po):
                fs = best_split_per_feature(
                    h, s, nb_full, ic_full, hn_full, lp_v, monotone,
                    bd if use_mc else None, d, parent_out=po)
                return fs.gain
            gains = jax.vmap(one_local)(_dqh(hists), lsum_loc, bounds,
                                        depths, pouts)
            gains = jnp.where(fms, gains, NEG_INF)
            # 2. local top-k vote -> one all_gather of (k, top_k) ids
            _, top_ids = jax.lax.top_k(gains, TOPK_V)
            all_ids = strat.vote_allgather(top_ids)   # (k_sc, kl, TOPK_V)
            # 3. global voting; ties break toward the lower feature index
            #    (GlobalVoting, voting_parallel_tree_learner.cpp:151)
            votes = jnp.zeros((kl, F), jnp.float32).at[
                jnp.arange(kl)[None, :, None], all_ids].add(
                    1.0, mode="drop")
            anti = -jnp.arange(F, dtype=jnp.float32) * 1e-6
            _, selected = jax.lax.top_k(votes + anti[None, :], SEL_V)
            # ascending order: at 2k >= F this is the identity map, and
            # argmax's first-max tie-break matches the full scan's
            selected = jnp.sort(selected, axis=1)
            # 4. merge ONLY the selected slices; dequantize after the
            #    exact integer sum (same ordering contract as scatter)
            sel_raw = jnp.take_along_axis(
                hists, selected[:, :, None, None], axis=1)
            hist_sel = _dqh(strat.reduce_hist_voted(sel_raw))
            # 5. full-constraint scan on the merged slices
            nb_v = nb_full[selected]
            ic_v = ic_full[selected]
            hn_v = hn_full[selected]
            mono_v = monotone[selected]
            fm_v = jnp.take_along_axis(fms, selected, axis=1)
            pen = cegb2 if cegb2 is not None else (
                jnp.broadcast_to(cegb, fms.shape)
                if cegb is not None else None)
            pen_v = None if pen is None else \
                jnp.take_along_axis(pen, selected, axis=1)
            contri_v = None if contri is None else contri[selected]
            rb_v = None if rbs is None else \
                jnp.take_along_axis(rbs, selected, axis=1)

            def one_sel(h, s, nb_, ic_, hn_, fm, mo, bd, d, po, *rest):
                it = iter(rest)
                pr = next(it) if pen_v is not None else None
                ct = next(it) if contri_v is not None else None
                rb = next(it) if rb_v is not None else None
                return local_best_candidate(
                    h, s, nb_, ic_, hn_, fm, sp, mo,
                    bd if use_mc else None, d, pr, ct, po, rb)
            extras = [a for a in (pen_v, contri_v, rb_v) if a is not None]
            g, f_loc, b, dl, ls, rs, member = jax.vmap(one_sel)(
                hist_sel, sums, nb_v, ic_v, hn_v, fm_v, mono_v, bounds,
                depths, pouts, *extras)
            f_glob = jnp.take_along_axis(
                selected, f_loc[:, None], axis=1)[:, 0]
            return (g, f_glob, b, dl, ls, rs, member)

        def many_candidates(hists, sums, bounds, depths, pouts, fms,
                            rbs=None, cegb2=None):
            """Best-split candidates for k leaves in one vmapped scan.
            ``fms`` is the per-child feature mask (k, F); ``rbs`` the
            per-child ExtraTrees random threshold bins (k, F) or None;
            ``cegb2`` an optional per-child (k, F) CEGB penalty vector
            (lazy costs) overriding the shared one.

            Scatter mode: ``hists`` arrive as this shard's feature block
            (k, FB_SC, Bb, 3); every per-feature operand is sliced to the
            same block, the scan runs on 1/k of the features, and the
            winner exchange combines the block-local bests into globally
            consistent candidates (global feature indices)."""
            cegb = getattr(strat, "cegb_full", None)
            contri = getattr(strat, "contri_full", None)
            if use_voting:
                return _voting_candidates(hists, sums, bounds, depths,
                                          pouts, fms, rbs, cegb2, cegb,
                                          contri)
            if use_efb:
                return _efb_candidates(hists, sums, bounds, depths, pouts,
                                       fms, rbs, cegb2, cegb, contri)
            if use_scatter:
                nb_s, ic_s, hn_s, mono_s = nb_sc, ic_sc, hn_sc, mono_sc
                fms = _slf2(fms, False)
                if rbs is not None:
                    rbs = _slf2(rbs, 0)
                if cegb2 is not None:
                    cegb2 = _slf2(cegb2, 0.0)
                if cegb is not None:
                    cegb = _slf(cegb, 0.0)
                if contri is not None:
                    contri = _slf(contri, 1.0)
            else:
                nb_s, ic_s, hn_s, mono_s = nb_full, ic_full, hn_full, \
                    monotone
            if cegb2 is not None:
                if rbs is None:
                    def one(h, s, bd, d, po, fm, cg):
                        return local_best_candidate(
                            h, s, nb_s, ic_s, hn_s, fm, sp,
                            mono_s, bd if use_mc else None, d, cg,
                            contri, po)
                    out = jax.vmap(one)(hists, sums, bounds, depths,
                                        pouts, fms, cegb2)
                else:
                    def one(h, s, bd, d, po, fm, cg, rb):
                        return local_best_candidate(
                            h, s, nb_s, ic_s, hn_s, fm, sp,
                            mono_s, bd if use_mc else None, d, cg, contri,
                            po, rb)
                    out = jax.vmap(one)(hists, sums, bounds, depths,
                                        pouts, fms, cegb2, rbs)
            elif rbs is None:
                def one(h, s, bd, d, po, fm):
                    return local_best_candidate(
                        h, s, nb_s, ic_s, hn_s, fm, sp,
                        mono_s, bd if use_mc else None, d, cegb, contri,
                        po)
                out = jax.vmap(one)(hists, sums, bounds, depths, pouts,
                                    fms)
            else:
                def one(h, s, bd, d, po, fm, rb):
                    return local_best_candidate(
                        h, s, nb_s, ic_s, hn_s, fm, sp,
                        mono_s, bd if use_mc else None, d, cegb, contri,
                        po, rb)
                out = jax.vmap(one)(hists, sums, bounds, depths, pouts,
                                    fms, rbs)
            return _exchange(out) if use_scatter else out

        # per-node RNG streams (bynode sampling / ExtraTrees thresholds),
        # identical on every DP shard (replicated key, identical node ids)
        if use_bynode or use_et:
            nk = node_key if node_key is not None else \
                jnp.zeros((2, 2), jnp.uint32)
        if use_bynode:
            def node_mask_many(ids):
                def one(i):
                    r = jax.random.uniform(jax.random.fold_in(nk[0], i),
                                           (F,))
                    kth = jax.lax.top_k(r, kcnt)[0][-1]
                    return r >= kth
                return jax.vmap(one)(ids)
        if use_et:
            et_hi = jnp.maximum(
                jnp.where(ic_full, nb_full - 1, nb_full - 2), 0)

            def node_rand_many(ids):
                def one(i):
                    u = jax.random.uniform(jax.random.fold_in(nk[1], i),
                                           (F,))
                    return jnp.minimum(
                        (u * (et_hi + 1).astype(jnp.float32)
                         ).astype(jnp.int32), et_hi)
                return jax.vmap(one)(ids)

        rl_dtype = jnp.uint8 if L <= 256 else jnp.int32

        def _spec_state():
            """Speculative-ramp initial state: provisional subtree from a
            row subsample, verified and committed against one full-data
            W-channel histogram pass (see make_wave_grow_fn docnotes).
            Replaces the root pass + the first ~log2(W) ramp waves.

            Data-parallel: each shard strides its LOCAL rows (the global
            subsample budget divides by ``spec_shards``) and every
            provisional pass psums its (W, G, Bb, 3) histogram batch over
            the mesh — exactly one extra collective per provisional pass,
            the same payload shape as a committed wave's — so all shards
            grow one identical provisional tree; the verification pass
            and commit tests then run on psum'd full-data sums."""
            import math as _m
            Kc, K1 = W, W - 1
            # -- statically-strided row subsample (an out-of-bag row's
            # weight levels are 0, so it adds nothing to any pass; a
            # grower built for a booster that samples leaves it out of
            # every pass, ``leaf_hists``) --
            stride = max(1, n // max(int(spec_subsample) // spec_shards,
                                     4096))
            n_ss = max((n // stride) // 4096 * 4096, 4096)
            w_src = wch0 if quantized else w8
            if pack4:
                # stride over packed BYTES: the subsample keeps adjacent
                # row pairs (one byte each) so the packed kernels consume
                # it directly; weights follow the same pair selection
                X_ss = X_T[:, ::stride][:, :n_ss // 2]
                w_ss = w_src.reshape(w_src.shape[0], -1, 2)[
                    :, ::stride][:, :n_ss // 2].reshape(w_src.shape[0],
                                                        n_ss)
            else:
                X_ss = X_T[:, ::stride][:, :n_ss]
                w_ss = w_src[:, ::stride][:, :n_ss]
            R_ss = router_bins(X_ss)
            # the count level of the subsample's weights
            in_bag_ss = w_ss[2 if quantized else 4] > 0 if sampled else None
            nan_of = jnp.where(hn_full, nb_full - 1, -1)       # (F,)
            fm_k = jnp.broadcast_to(feature_mask, (Kc, F))
            jar = jnp.arange(Kc, dtype=jnp.int32)
            zb_k = jnp.zeros((Kc, 2), jnp.float32)
            zd_k = jnp.zeros((Kc,), jnp.int32)

            def dqh(h):
                return dq(h) if quantized else h

            # -- provisional growth on the subsample: each wave histograms
            # EVERY current prov leaf (rl_ss doubles as the channel id),
            # scans, and splits all positive-gain leaves up to capacity --
            rl_ss = jnp.zeros((n_ss,), jnp.uint8)
            nlp = jnp.asarray(1, jnp.int32)
            pfeat = jnp.zeros((K1,), jnp.int32)
            pthr = jnp.zeros((K1,), jnp.int32)
            pnan = jnp.full((K1,), -1, jnp.int32)
            pdl = jnp.zeros((K1,), jnp.int32)
            pleaf = jnp.zeros((K1,), jnp.int32)
            pact = jnp.zeros((K1,), jnp.bool_)
            ppar = jnp.full((K1,), -1, jnp.int32)
            owner = jnp.full((Kc,), -1, jnp.int32)
            Lm = jnp.zeros((K1, Kc), jnp.bool_)   # left-descendant leaves
            Rm = jnp.zeros((K1, Kc), jnp.bool_)   # right-descendant leaves
            tabs = []
            for _t in range(max(1, int(_m.ceil(_m.log2(Kc))))):
                h_ss = leaf_hists(X_ss, w_ss, rl_ss.astype(jnp.int8),
                                  in_bag_ss, False)[0][:Kc]
                # DP: the one histogram collective of this provisional
                # pass — the provisional batches ride the same merge mode
                # as committed waves (psum, or the feature-sliced
                # reduce-scatter), so every shard grows the same
                # provisional tree (serial: identity).  Leaf totals come
                # from _reduce_waves so they are shard-consistent under
                # scatter.
                h_ss, sums_pl = _reduce_waves(h_ss, Kc, with_totals=True)
                lvp = leaf_output(sums_pl[:, 0], sums_pl[:, 1], sp)
                cnds = many_candidates(
                    _scan_hists(h_ss, sums_pl), sums_pl,
                    zb_k, zd_k, lvp, fm_k)
                g = jnp.where(jar < nlp, cnds[0], NEG_INF)
                vals, sel_l = jax.lax.top_k(g, Kc)
                sel = (vals > 0) & (jar < Kc - nlp)
                prefix = jnp.cumsum(sel.astype(jnp.int32))
                newids = nlp + prefix - 1
                nodeids = (nlp - 1) + prefix - 1
                feat_s = cnds[1][sel_l]
                thr_s = cnds[2][sel_l]
                dl_s = cnds[3][sel_l].astype(jnp.int32)
                fnan_s = nan_of[feat_s]
                nidx = jnp.where(sel, nodeids, K1)
                pfeat = pfeat.at[nidx].set(feat_s, mode="drop")
                pthr = pthr.at[nidx].set(thr_s, mode="drop")
                pnan = pnan.at[nidx].set(fnan_s, mode="drop")
                pdl = pdl.at[nidx].set(dl_s, mode="drop")
                pleaf = pleaf.at[nidx].set(sel_l, mode="drop")
                pact = pact.at[nidx].set(sel, mode="drop")
                ppar = ppar.at[nidx].set(owner[sel_l], mode="drop")
                # descendant propagation: nodes holding leaf r gain leaf s
                A = jnp.zeros((Kc, Kc), jnp.int32).at[
                    jnp.where(sel, sel_l, Kc),
                    jnp.where(sel, newids, Kc)].set(1, mode="drop")
                Lm = Lm | (Lm.astype(jnp.int32) @ A > 0)
                Rm = Rm | (Rm.astype(jnp.int32) @ A > 0)
                oh_l = jax.nn.one_hot(sel_l, Kc, dtype=jnp.bool_)
                oh_r = jax.nn.one_hot(newids, Kc, dtype=jnp.bool_)
                Lm = Lm.at[nidx].set(oh_l, mode="drop")
                Rm = Rm.at[nidx].set(oh_r, mode="drop")
                owner = owner.at[jnp.where(sel, sel_l, Kc)].set(
                    nodeids, mode="drop")
                owner = owner.at[jnp.where(sel, newids, Kc)].set(
                    nodeids, mode="drop")
                feats_cl = jnp.clip(feat_s, 0, F - 1)
                tab = jnp.stack([
                    thr_s, fnan_s, dl_s, jnp.ones((Kc,), jnp.int32),
                    sel_l, newids, sel.astype(jnp.int32),
                    jnp.zeros((Kc,), jnp.int32)])
                rl2, _ = route_rows(R_ss, feats_cl, rl_ss, tab)
                rl_ss = rl2.astype(jnp.uint8)
                tabs.append((tab, feats_cl))
                nlp = nlp + prefix[-1]

            # -- route ALL rows through the provisional tree (same
            # per-wave fused kernel the real row update uses, so the
            # partition matches how committed splits will route) --
            rl_full = jnp.zeros((n,), jnp.uint8)
            for tab, feats_cl in tabs:
                rlf, _ = route_rows(X_R, feats_cl, rl_full, tab)
                rl_full = rlf.astype(jnp.uint8)

            # -- ONE full-data pass: exact per-prov-leaf channel sums --
            (h_ch, leaf_tot), counts_v = hist_waves(
                rl_full.astype(jnp.int8), k=Kc, with_totals=True)  # (Kc, 3)
            # voting: keep the batch RAW and shard-local — the node-sum
            # einsum is exact in int32 and _voting_candidates merges
            # (and dequantizes) only the voted slices
            hf_ch = h_ch if use_voting else dqh(h_ch)

            # -- exact node aggregates + commit tests --
            lt3 = Lm.astype(jnp.float32) @ leaf_tot          # (K1, 3)
            rt3 = Rm.astype(jnp.float32) @ leaf_tot
            pt3 = lt3 + rt3
            Dn = Lm | Rm
            H_node = jnp.einsum("jl,lgbc->jgbc",
                                Dn.astype(hf_ch.dtype), hf_ch)
            lvn = leaf_output(pt3[:, 0], pt3[:, 1], sp)
            bg = many_candidates(
                H_node if use_voting else
                jax.vmap(expand_hist)(H_node, pt3), pt3,
                jnp.zeros((K1, 2), jnp.float32),
                jnp.zeros((K1,), jnp.int32), lvn,
                jnp.broadcast_to(feature_mask, (K1, F)))[0]

            def lg3(s3):
                return _leaf_gain(s3[:, 0], s3[:, 1],
                                  sp.lambda_l1, sp.lambda_l2)

            pg = lg3(lt3) + lg3(rt3) - (lg3(pt3) + sp.min_gain_to_split)
            okc = ((lt3[:, 2] >= sp.min_data_in_leaf) &
                   (rt3[:, 2] >= sp.min_data_in_leaf) &
                   (lt3[:, 1] >= sp.min_sum_hessian_in_leaf) &
                   (rt3[:, 1] >= sp.min_sum_hessian_in_leaf))
            test = (pact & okc & (pg > 0) &
                    (pg >= (1.0 - spec_tol) * jnp.maximum(bg, 0.0)))
            comm = jnp.zeros((K1,), jnp.bool_)
            for j in range(K1):  # parents precede children by construction
                pok = jnp.where(ppar[j] < 0, True,
                                comm[jnp.maximum(ppar[j], 0)])
                comm = comm.at[j].set(pok & test[j])

            # -- replay committed nodes into the wave-state arrays (same
            # leaf/node numbering convention as the wave body: left child
            # keeps the split leaf's id, right child takes the next
            # fresh id; child slots encode leaves as -(leaf+1)) --
            s_map = jnp.zeros((Kc,), jnp.int32)   # prov leaf -> state leaf
            depth_pl = jnp.zeros((Kc,), jnp.int32)
            nl_run = jnp.asarray(1, jnp.int32)
            sf = jnp.full((L - 1,), -1, jnp.int32)
            tb_ = jnp.zeros((L - 1,), jnp.int32)
            nb_ = jnp.full((L - 1,), -1, jnp.int32)
            dt_ = jnp.zeros((L - 1,), jnp.int32)
            lc_ = jnp.zeros((L - 1,), jnp.int32)
            rc_ = jnp.zeros((L - 1,), jnp.int32)
            sg_ = jnp.zeros((L - 1,), jnp.float32)
            iv_ = jnp.zeros((L - 1,), jnp.float32)
            iw_ = jnp.zeros((L - 1,), jnp.float32)
            ic_ = jnp.zeros((L - 1,), jnp.float32)
            for j in range(K1):
                cj = comm[j]
                sl = s_map[pleaf[j]]
                new_leaf = nl_run
                nid = nl_run - 1
                enc = -(sl + 1)
                lc_ = jnp.where(cj & (lc_ == enc), nid, lc_)
                rc_ = jnp.where(cj & (rc_ == enc), nid, rc_)
                nidx = jnp.where(cj, nid, L - 1)
                sf = sf.at[nidx].set(pfeat[j], mode="drop")
                tb_ = tb_.at[nidx].set(pthr[j], mode="drop")
                nb_ = nb_.at[nidx].set(pnan[j], mode="drop")
                dt_ = dt_.at[nidx].set(
                    jnp.where(pdl[j] > 0, DEFAULT_LEFT_MASK, 0) |
                    jnp.where(pnan[j] >= 0, MISSING_NAN, 0), mode="drop")
                lc_ = lc_.at[nidx].set(enc, mode="drop")
                rc_ = rc_.at[nidx].set(-(new_leaf + 1), mode="drop")
                sg_ = sg_.at[nidx].set(pg[j], mode="drop")
                iv_ = iv_.at[nidx].set(
                    leaf_output(pt3[j, 0], pt3[j, 1], sp), mode="drop")
                iw_ = iw_.at[nidx].set(pt3[j, 1], mode="drop")
                ic_ = ic_.at[nidx].set(pt3[j, 2], mode="drop")
                s_map = jnp.where(cj & Rm[j], new_leaf, s_map)
                depth_pl = jnp.where(cj & Dn[j], depth_pl + 1, depth_pl)
                nl_run = nl_run + cj.astype(jnp.int32)

            # -- pools + frontier candidates --
            rl0 = jnp.take(s_map, rl_full.astype(jnp.int32))
            hists0 = jnp.zeros(
                (L, G_loc, Bb, HC), h_ch.dtype).at[s_map].add(h_ch[:Kc])
            lsum0 = jnp.zeros((L, 3), jnp.float32).at[s_map].add(leaf_tot)
            ldep0 = jnp.zeros((L,), jnp.int32).at[s_map].set(depth_pl)
            live = jnp.arange(L, dtype=jnp.int32) < nl_run
            lval0 = jnp.where(live, leaf_output(lsum0[:, 0], lsum0[:, 1],
                                                sp), 0.0)
            cnds0 = many_candidates(
                _scan_hists(hists0[:Kc], lsum0[:Kc]),
                lsum0[:Kc], zb_k, ldep0[:Kc], lval0[:Kc], fm_k)
            cg0 = jnp.where(jar < nl_run, cnds0[0], NEG_INF)
            return {
                "row_leaf": rl0.astype(rl_dtype),
                "leaf_sum": lsum0,
                "leaf_depth": ldep0,
                "cand_gain": jnp.full((L,), NEG_INF,
                                      jnp.float32).at[:Kc].set(cg0),
                "cand_feat": jnp.zeros((L,), jnp.int32).at[:Kc].set(
                    cnds0[1]),
                "cand_bin": jnp.zeros((L,), jnp.int32).at[:Kc].set(
                    cnds0[2]),
                "cand_dleft": jnp.zeros((L,), jnp.bool_).at[:Kc].set(
                    cnds0[3]),
                "cand_lsum": jnp.zeros((L, 3), jnp.float32).at[:Kc].set(
                    cnds0[4]),
                "cand_rsum": jnp.zeros((L, 3), jnp.float32).at[:Kc].set(
                    cnds0[5]),
                "cand_member": jnp.zeros((L, max_bins),
                                         jnp.bool_).at[:Kc].set(cnds0[6]),
                "hists": hists0,
                "split_feature": sf, "threshold_bin": tb_, "nan_bin": nb_,
                "cat_member": jnp.zeros((L - 1, max_bins), jnp.bool_),
                "decision_type": dt_, "left_child": lc_, "right_child": rc_,
                "split_gain": sg_, "internal_value": iv_,
                "internal_weight": iw_, "internal_count": ic_,
                "leaf_value": lval0,
                "leaf_weight": jnp.where(live, lsum0[:, 1], 0.0),
                "leaf_count": jnp.where(live, lsum0[:, 2], 0.0),
                "num_leaves": nl_run,
                "done": jnp.asarray(False),
                # full-data histogram passes so far: the one verification
                # mega-pass over the provisional leaves (the ~log2(W)
                # provisional passes run at subsample scale and are not
                # counted)
                **first_pass(nlp, counts_v),
            }, jnp.stack([jnp.sum(in_bag_ss, dtype=jnp.int32) if sampled
                          else jnp.int32(n_ss), jnp.int32(n_ss)])

        if use_spec:
            with jax.named_scope("lgbm.ramp"):
                state, ramp_sample = _spec_state()
        else:
            ramp_sample = jnp.zeros((2,), jnp.int32)
            # ---- root ----
            with jax.named_scope("lgbm.root"):
                if quantized:
                    # derive the root totals from the quantized histogram
                    # itself (any bundle's bins sum to the total, and the
                    # integer sum is exact BEFORE dequantization — identical
                    # for every feature, shard and merge mode) so candidate
                    # left+right sums stay consistent with the totals
                    # downstream
                    (rh, rtot), counts_r = hist_waves(
                        jnp.zeros((n,), jnp.int8), k=1, with_totals=True)
                    root_hist = rh[0]
                    root_sum = rtot[0]
                else:
                    rh, counts_r = hist_waves(jnp.zeros((n,), jnp.int8), k=1)
                    root_hist = rh[0]
                    root_sum = strat.reduce_sum(jnp.stack([
                        jnp.sum(gm), jnp.sum(hm), jnp.sum(cnt_mask)]))
                root_hist_f = dq(root_hist) if quantized else root_hist
                root_bound = jnp.asarray([-BIG, BIG], jnp.float32)
                root_out = _child_out(root_sum[0], root_sum[1], root_sum[2],
                                      jnp.asarray(0.0, jnp.float32))
                rid = jnp.asarray([2 * L], jnp.int32)
                fm_root = feature_mask
                if use_ic:
                    fm_root = fm_root & allowed_features(
                        jnp.zeros((F,), jnp.bool_))
                if use_bynode:
                    fm_root = fm_root & node_mask_many(rid)[0]
                rb_root = node_rand_many(rid)[0] if use_et else None
                if use_lazy:
                    # Charge only rows whose feature bit is still unset in the
                    # PERSISTENT used bitmap (cost_effective_gradient_boosting.hpp
                    # CalculateOndemandCosts): from the second tree on, features
                    # already materialized by earlier trees' splits cost nothing
                    # for those rows.  used_root[f] = in-bag rows with bit set.
                    # Like cnt_group below, the f32-accumulated 0/1 dot is exact
                    # to 2^24 counted rows per shard; beyond that the lazy cost
                    # degrades gracefully (it only biases split selection).
                    base = strat.cegb_full if strat.cegb_full is not None else 0.0
                    used0 = lazy_used if lazy_used is not None \
                        else lazy_bitmap_init(F, n, lp)
                    used_root = strat.reduce_sum(jax.lax.dot_general(
                        (_unpack_bits(used0) if lp
                         else used0).astype(jnp.bfloat16),
                        (bag_mask > 0).astype(jnp.bfloat16)[None, :],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)[:, 0])       # (F,)
                    strat.cegb_full = base + lazy_pen * jnp.maximum(
                        root_sum[2] - used_root, 0.0)
                if use_scatter or use_voting or use_efb:
                    # the root scan rides the sliced/voted many_candidates
                    # path (a 1-channel batch) so it too scans only this
                    # shard's block (scatter), merges only the voted
                    # feature slices (voting) or reads the members where
                    # they lie in bundle space (EFB)
                    c1 = many_candidates(
                        _scan_hists(root_hist[None], root_sum[None]),
                        root_sum[None], root_bound[None],
                        jnp.zeros((1,), jnp.int32), root_out[None],
                        fm_root[None],
                        rb_root[None] if rb_root is not None else None)
                    cand = tuple(a[0] for a in c1)
                else:
                    cand = strat.leaf_candidates(
                        expand_hist(root_hist_f, root_sum), root_sum, fm_root,
                        sp, root_bound, jnp.asarray(0, jnp.int32), root_out,
                        rb_root)

                state = {
                    "row_leaf": jnp.zeros((n,), rl_dtype),
                    "leaf_sum": jnp.zeros((L, 3), jnp.float32).at[0].set(root_sum),
                    "leaf_depth": jnp.zeros((L,), jnp.int32),
                    "cand_gain": jnp.full((L,), NEG_INF, jnp.float32).at[0].set(cand[0]),
                    "cand_feat": jnp.zeros((L,), jnp.int32).at[0].set(cand[1]),
                    "cand_bin": jnp.zeros((L,), jnp.int32).at[0].set(cand[2]),
                    "cand_dleft": jnp.zeros((L,), jnp.bool_).at[0].set(cand[3]),
                    "cand_lsum": jnp.zeros((L, 3), jnp.float32).at[0].set(cand[4]),
                    "cand_rsum": jnp.zeros((L, 3), jnp.float32).at[0].set(cand[5]),
                    "cand_member": jnp.zeros((L, max_bins), jnp.bool_).at[0].set(
                        cand[6]),
                    "hists": jnp.zeros(
                        (L, G_loc, Bb, HC),
                        jnp.int32 if quantized else jnp.float32).at[0].set(
                            root_hist),
                    "split_feature": jnp.full((L - 1,), -1, jnp.int32),
                    "threshold_bin": jnp.zeros((L - 1,), jnp.int32),
                    "nan_bin": jnp.full((L - 1,), -1, jnp.int32),
                    "cat_member": jnp.zeros((L - 1, max_bins), jnp.bool_),
                    "decision_type": jnp.zeros((L - 1,), jnp.int32),
                    "left_child": jnp.zeros((L - 1,), jnp.int32),
                    "right_child": jnp.zeros((L - 1,), jnp.int32),
                    "split_gain": jnp.zeros((L - 1,), jnp.float32),
                    "internal_value": jnp.zeros((L - 1,), jnp.float32),
                    "internal_weight": jnp.zeros((L - 1,), jnp.float32),
                    "internal_count": jnp.zeros((L - 1,), jnp.float32),
                    "leaf_value": jnp.zeros((L,), jnp.float32).at[0].set(root_out),
                    "leaf_weight": jnp.zeros((L,), jnp.float32).at[0].set(root_sum[1]),
                    "leaf_count": jnp.zeros((L,), jnp.float32).at[0].set(root_sum[2]),
                    "num_leaves": jnp.asarray(1, jnp.int32),
                    "done": jnp.asarray(False),
                    **first_pass(1, counts_r),                 # the root pass
                }
                if use_mc:
                    state["leaf_mn"] = jnp.full((L,), -BIG, jnp.float32)
                    state["leaf_mx"] = jnp.full((L,), BIG, jnp.float32)
                    if mc_inter:
                        # per-leaf bin-space region boxes for the geometric
                        # contiguity test of the intermediate constraints
                        state["leaf_lo"] = jnp.zeros((L, F), jnp.int32)
                        state["leaf_hi"] = jnp.broadcast_to(
                            (nb_full - 1).astype(jnp.int32)[None, :],
                            (L, F)).copy()
                if use_ic:
                    # features used on the path to each leaf (interaction
                    # constraints restrict children to compatible groups)
                    state["leaf_path"] = jnp.zeros((L, F), jnp.bool_)
                if use_lazy:
                    # per-(feature, row) "already computed" bitmap — PERSISTENT
                    # across trees like the reference's feature_used_in_data_
                    # bitset (it is allocated once per training run and never
                    # cleared); the learner threads it through every grow call.
                    # Packed to uint8 bitfields (lazy_bitmap_init) — 8x less
                    # HBM than the former bool layout; lazy_bitpack=False
                    # keeps the bool path (tests cross-check equality).
                    state["used"] = lazy_used if lazy_used is not None \
                        else lazy_bitmap_init(F, n, lp)

        jarange = jnp.arange(W, dtype=jnp.int32)

        def body(s, forced=None):
            # ---- the wave's leaves: top-W by gain (or the forced ones) ----
            with jax.named_scope("lgbm.wave.commit"):
                nl0 = s["num_leaves"]
                if forced is None:
                    budget = L - nl0
                    # Endgame taper: committing a full wave close to the leaf
                    # budget would lock in splits that freshly-created children
                    # (whose gains are not yet known) should have outcompeted —
                    # the sequential best-first order lets them.  Halving the
                    # wave once budget < 2W closes most of the quality gap to
                    # the exact order; the W//4 floor caps the halving cascade
                    # at ~2-3 extra waves (each wave is a full-data histogram
                    # pass — a log2(W)-deep taper costs more wall time than
                    # its last few splits are worth).
                    k_eff = wave_taper_k(budget, W)
                    vals, sel_leaves = jax.lax.top_k(s["cand_gain"], W)
                    sel = (vals > 0) & (jarange < k_eff)
                    feat = s["cand_feat"][sel_leaves]          # (W,)
                    thr = s["cand_bin"][sel_leaves]
                    dleft = s["cand_dleft"][sel_leaves]
                    lsum = s["cand_lsum"][sel_leaves]          # (W, 3)
                    rsum = s["cand_rsum"][sel_leaves]
                    member = s["cand_member"][sel_leaves]      # (W, B)
                    psum_ = s["leaf_sum"][sel_leaves]
                else:
                    # forced wave: fixed (leaf, feature, bin) applied
                    # regardless of gain; child sums read from the parent's
                    # pooled histogram (the partitioned grower's ForceSplits
                    # override, learner/partitioned.py:440, batched)
                    import numpy as _np
                    k = len(forced)
                    pad = [(0, 0, 0)] * (W - k)
                    trip = _np.asarray(list(forced) + pad, _np.int32)
                    sel_leaves = jnp.asarray(trip[:, 0])
                    feat = jnp.asarray(trip[:, 1])
                    thr = jnp.asarray(trip[:, 2])
                    psum_ = s["leaf_sum"][jnp.asarray(trip[:, 0])]
                    # empty forced leaves are skipped like the partitioned
                    # grower's `do = leaf_seg > 0` gate (degenerate forcing
                    # files route all rows one way; the reference stops
                    # forcing such subtrees too)
                    sel = jnp.asarray(_np.arange(W) < k) & (psum_[:, 2] > 0)
                    dleft = jnp.zeros((W,), jnp.bool_)
                    member = jnp.zeros((W, max_bins), jnp.bool_)
                    ph = s["hists"][sel_leaves]
                    phf = dq(ph) if quantized else ph
                    exh = jax.vmap(expand_hist)(phf, psum_)    # (W, F, B, 3)
                    fh = exh[jnp.arange(W), feat]              # (W, B, 3)
                    csum = jnp.cumsum(fh, axis=1)
                    lsum = csum[jnp.arange(W),
                                jnp.clip(thr, 0, max_bins - 1)]
                    rsum = psum_ - lsum
                    # record the forced split's REAL gain (the reference's
                    # ForceSplits computes a full SplitInfo for the forced
                    # threshold), on the scan's shifted-gain scale
                    vals = (_leaf_gain(lsum[:, 0], lsum[:, 1],
                                       sp.lambda_l1, sp.lambda_l2) +
                            _leaf_gain(rsum[:, 0], rsum[:, 1],
                                       sp.lambda_l1, sp.lambda_l2) -
                            _leaf_gain(psum_[:, 0], psum_[:, 1],
                                       sp.lambda_l1, sp.lambda_l2) -
                            sp.min_gain_to_split)
                prefix = jnp.cumsum(sel.astype(jnp.int32))
                total_new = prefix[-1]
                new_ids = nl0 + prefix - 1                     # valid where sel
                node_ids = (nl0 - 1) + prefix - 1              # node index
                left_smaller = lsum[:, 2] <= rsum[:, 2]        # (W,)
                fcat = ic_full[feat]
                fnan = hn_full[feat]
                f_nan_bin = jnp.where(fnan, nb_full[feat] - 1, -1)

            # ---- row_leaf + wave-channel update ----
            with jax.named_scope("lgbm.wave.row_update"):
                rl = s["row_leaf"]
                rl_old = rl
                if fused_update:
                    # one fused kernel pass instead of W masked XLA sweeps
                    # (each sweep's fused-loop launch overhead alone costs
                    # ~0.7 ms at 10.5M rows); a data set with categorical
                    # columns hands it the slots' left sets as bit sets,
                    # and so does a bundled one: a split on a member of a
                    # bundle is the set of the bundle column's codes that
                    # go left, and the kernel fetches the BUNDLE's column
                    tab = jnp.stack([
                        thr, f_nan_bin, dleft.astype(jnp.int32),
                        left_smaller.astype(jnp.int32), sel_leaves, new_ids,
                        sel.astype(jnp.int32), jnp.zeros_like(thr)])
                    if use_efb:
                        bundled, go = bundle_left_sets(
                            efb_arrays, feat, thr, f_nan_bin, dleft)
                        sets = jnp.pad(member, ((0, 0), (0, 256 - max_bins)))
                        slots = (fcat | bundled,
                                 jnp.where(bundled[:, None], go, sets))
                        rl_new, ch = route_rows(X_R, f_bundle[feat], rl, tab,
                                                cat=slots)
                    else:
                        rl_new, ch = route_rows(
                            X_R, feat, rl, tab,
                            cat=(fcat, member) if any_cat else None)
                    rl = rl_new.astype(rl.dtype)
                else:
                    # Vectorized XLA form, for what the fused kernel does
                    # not take (more than 255 bins in a feature or a
                    # bundle column, a histogram implementation other
                    # than Pallas; EFB bundles take the kernel since PR
                    # 38) and, off the TPU, the kernel's test oracle:
                    # every row belongs
                    # to at most one split leaf, so an argmax over the
                    # (W, N) match matrix picks its slot and a single
                    # take_along_axis resolves the decision.  (The [old]
                    # timings this comment carried were of shapes a TPU no
                    # longer routes this way.)
                    if small_bins:
                        thr_c = thr.astype(jnp.uint8)[:, None]
                        nan_c = jnp.where(f_nan_bin < 0, 255,
                                          f_nan_bin).astype(jnp.uint8)[:, None]
                    else:
                        thr_c = thr[:, None]
                        nan_c = f_nan_bin[:, None]
                    sel_c = sel_leaves.astype(rl.dtype)
                    mi8 = member.astype(jnp.int8).T                # (B, W)
                    cat_static = sp.cat_idx if any_cat else ()

                    def _upd_block(Xb, rlb):
                        """One row block of the batched update — (W, m)
                        intermediates stay bounded for very large N."""
                        m = Xb.shape[1]

                        def fcol(ff):
                            g = f_bundle[ff] if use_efb else ff
                            v = jax.lax.dynamic_slice(Xb, (g, 0), (1, m))[0]
                            if small_bins:
                                return v
                            return bundle_decode(v.astype(jnp.int32), ff)

                        cols_w = jax.vmap(fcol)(feat)              # (W, m)
                        num_go = jnp.where(cols_w == nan_c, dleft[:, None],
                                           cols_w <= thr_c)
                        if not any_cat:
                            go_w = num_go
                        elif 0 < len(cat_static) <= 8:
                            # per-slot bitset lookup as FEW-INDICES x
                            # WIDE-ROW embedding takes: N row-takes from
                            # the transposed (B, W) table instead of a
                            # (W, N)-indexed gather from the (W, B) one
                            # (7x apart [old]; Pallas shapes with 255 bins
                            # or fewer take the kernel above instead) —
                            # loop the STATIC cat features, combine by
                            # split-feature match
                            acc = jnp.zeros((m, W), jnp.int8)
                            for cf in cat_static:
                                colv = fcol(jnp.asarray(cf, jnp.int32))
                                look = jnp.take(mi8, colv.astype(jnp.int32),
                                                axis=0)            # (m, W)
                                acc = acc + look * (feat == cf).astype(
                                    jnp.int8)[None, :]
                            go_w = jnp.where(fcat[:, None], acc.T > 0, num_go)
                        else:
                            go_w = jnp.where(
                                fcat[:, None],
                                jnp.take_along_axis(
                                    member, cols_w.astype(jnp.int32), axis=1),
                                num_go)
                        match = sel[:, None] & (rlb[None, :] == sel_c[:, None])
                        has = jnp.any(match, axis=0)               # (m,)
                        jhit = jnp.argmax(match, axis=0)
                        go = jnp.take_along_axis(go_w, jhit[None, :],
                                                 axis=0)[0]
                        chb = jnp.where(
                            has & (go == left_smaller[jhit]),
                            jhit.astype(jnp.int8), jnp.int8(-1))
                        rlb = jnp.where(has & jnp.logical_not(go),
                                        new_ids[jhit].astype(rlb.dtype), rlb)
                        return rlb, chb

                    blk = max(4096, ((1 << 26) // max(W, 1)) // 4096 * 4096)
                    if n <= blk:
                        rl, ch = _upd_block(X_T, rl)
                    else:
                        parts = [_upd_block(X_T[:, lo:lo + blk],
                                            rl[lo:lo + blk])
                                 for lo in range(0, n, blk)]
                        rl = jnp.concatenate([p_[0] for p_ in parts])
                        ch = jnp.concatenate([p_[1] for p_ in parts])

            # ---- one kernel pass: all W smaller-child histograms ----
            with jax.named_scope("lgbm.wave.hist"):
                hist_small, counts = hist_waves(ch, sparse=True)  # (W, G, Bb, 3)
                parents = s["hists"][sel_leaves]
                hist_big = parents - hist_small
                ls4 = left_smaller[:, None, None, None]
                hist_l = jnp.where(ls4, hist_small, hist_big)
                hist_r = jnp.where(ls4, hist_big, hist_small)

            # ---- children outputs (smoothed toward the split leaf's own
            # value under path_smooth) + monotone bounds
            # (BasicLeafConstraints::Update) ----
            with jax.named_scope("lgbm.wave.child_out"):
                parent_lv = s["leaf_value"][sel_leaves]
                out_l = _child_out(lsum[:, 0], lsum[:, 1], lsum[:, 2], parent_lv)
                out_r = _child_out(rsum[:, 0], rsum[:, 1], rsum[:, 2], parent_lv)
                if use_mc and mc_inter:
                    # Intermediate constraints (monotone_constraints.hpp:514
                    # IntermediateLeafConstraints): children are bounded by
                    # the SIBLING'S OUTPUT instead of the midpoint, and the
                    # new outputs propagate to every geometrically contiguous
                    # leaf.  The reference finds contiguous leaves by walking
                    # up the tree and filtering thresholds
                    # (GoUpToFindLeavesToUpdate / GoDownToFindLeavesToUpdate);
                    # here each leaf carries its bin-space region box
                    # (leaf_lo/leaf_hi), and contiguity is the EXACT geometric
                    # test — regions overlapping in every feature except one
                    # monotone feature where they are disjoint and ordered.
                    # The wave's W splits are refined sequentially over the
                    # SMALL (L,)-sized arrays (one histogram pass still serves
                    # the whole wave), so later slots see earlier slots'
                    # tightened bounds — within-wave batching stays safe.
                    mn_all, mx_all = s["leaf_mn"], s["leaf_mx"]
                    lo_all, hi_all = s["leaf_lo"], s["leaf_hi"]
                    out_l2 = jnp.zeros((W,), jnp.float32)
                    out_r2 = jnp.zeros((W,), jnp.float32)
                    bnd_l = jnp.zeros((W, 2), jnp.float32)
                    bnd_r = jnp.zeros((W, 2), jnp.float32)
                    inc_row = (monotone > 0)[None, :]
                    dec_row = (monotone < 0)[None, :]
                    for j in range(W):
                        act = sel[j]
                        p = sel_leaves[j]
                        fj = feat[j]
                        mj = jnp.where(fcat[j], 0, monotone[fj])
                        pmn, pmx = mn_all[p], mx_all[p]
                        ol = jnp.clip(out_l[j], pmn, pmx)
                        orr = jnp.clip(out_r[j], pmn, pmx)
                        # bounds tightened by earlier slots can cross a stale
                        # candidate's outputs; collapse to the shared boundary
                        # (monotone-safe, zero-gain degenerate split)
                        cross = ((mj > 0) & (ol > orr)) | ((mj < 0) & (ol < orr))
                        midj = (ol + orr) / 2.0
                        ol = jnp.where(cross, jnp.clip(midj, pmn, pmx), ol)
                        orr = jnp.where(cross, jnp.clip(midj, pmn, pmx), orr)
                        # child entries (UpdateConstraintsWithOutputs)
                        mn_lj = jnp.where(mj < 0, jnp.maximum(pmn, orr), pmn)
                        mx_lj = jnp.where(mj > 0, jnp.minimum(pmx, orr), pmx)
                        mn_rj = jnp.where(mj > 0, jnp.maximum(pmn, ol), pmn)
                        mx_rj = jnp.where(mj < 0, jnp.minimum(pmx, ol), pmx)
                        # child regions (categorical splits keep the parent box
                        # — no feature-order relation between cat children)
                        lo_p, hi_p = lo_all[p], hi_all[p]
                        num_j = jnp.logical_not(fcat[j])
                        hi_l = jnp.where(num_j, hi_p.at[fj].set(thr[j]), hi_p)
                        lo_r = jnp.where(num_j,
                                         lo_p.at[fj].set(thr[j] + 1), lo_p)
                        for c_lo, c_hi, c_out in ((lo_p, hi_l, ol),
                                                  (lo_r, hi_p, orr)):
                            inter = (lo_all <= c_hi[None, :]) & \
                                (hi_all >= c_lo[None, :])          # (L, F)
                            nfail = jnp.sum(jnp.logical_not(inter), axis=1)
                            onlyf = (nfail == 1)[:, None] & \
                                jnp.logical_not(inter)
                            below = onlyf & (hi_all < c_lo[None, :])
                            above = onlyf & (lo_all > c_hi[None, :])
                            capmax = jnp.any((below & inc_row) |
                                             (above & dec_row), axis=1)
                            capmin = jnp.any((above & inc_row) |
                                             (below & dec_row), axis=1)
                            mx_all = jnp.where(act & capmax,
                                               jnp.minimum(mx_all, c_out),
                                               mx_all)
                            mn_all = jnp.where(act & capmin,
                                               jnp.maximum(mn_all, c_out),
                                               mn_all)
                        pj = jnp.where(act, p, L)
                        rj = jnp.where(act, new_ids[j], L)
                        mn_all = mn_all.at[pj].set(mn_lj, mode="drop") \
                                       .at[rj].set(mn_rj, mode="drop")
                        mx_all = mx_all.at[pj].set(mx_lj, mode="drop") \
                                       .at[rj].set(mx_rj, mode="drop")
                        hi_all = hi_all.at[pj].set(hi_l, mode="drop") \
                                       .at[rj].set(hi_p, mode="drop")
                        lo_all = lo_all.at[rj].set(lo_r, mode="drop")
                        out_l2 = out_l2.at[j].set(ol)
                        out_r2 = out_r2.at[j].set(orr)
                        bnd_l = bnd_l.at[j].set(jnp.stack([mn_lj, mx_lj]))
                        bnd_r = bnd_r.at[j].set(jnp.stack([mn_rj, mx_rj]))
                    out_l, out_r = out_l2, out_r2
                    mn_l, mx_l = bnd_l[:, 0], bnd_l[:, 1]
                    mn_r, mx_r = bnd_r[:, 0], bnd_r[:, 1]
                    bounds2 = jnp.concatenate([bnd_l, bnd_r])   # (2W, 2)
                elif use_mc:
                    p_mn = s["leaf_mn"][sel_leaves]
                    p_mx = s["leaf_mx"][sel_leaves]
                    out_l = jnp.clip(out_l, p_mn, p_mx)
                    out_r = jnp.clip(out_r, p_mn, p_mx)
                    m = jnp.where(fcat, 0, monotone[feat])
                    mid = (out_l + out_r) / 2.0
                    mn_l = jnp.where(m < 0, jnp.maximum(p_mn, mid), p_mn)
                    mx_l = jnp.where(m > 0, jnp.minimum(p_mx, mid), p_mx)
                    mn_r = jnp.where(m > 0, jnp.maximum(p_mn, mid), p_mn)
                    mx_r = jnp.where(m < 0, jnp.minimum(p_mx, mid), p_mx)
                    bounds2 = jnp.concatenate([
                        jnp.stack([mn_l, mx_l], axis=1),
                        jnp.stack([mn_r, mx_r], axis=1)])       # (2W, 2)
                else:
                    bounds2 = jnp.zeros((2 * W, 2), jnp.float32)

            # ---- children candidates: one vmapped scan over 2W ----
            with jax.named_scope("lgbm.wave.scan"):
                child_depth = s["leaf_depth"][sel_leaves] + 1
                hists2 = jnp.concatenate([hist_l, hist_r])      # (2W, G, Bb, 3)
                sums2 = jnp.concatenate([lsum, rsum])
                totals2 = sums2
                ex2 = _scan_hists(hists2, totals2)
                depth2 = jnp.concatenate([child_depth, child_depth])
                lv2 = jnp.concatenate([out_l, out_r])
                fm2 = jnp.broadcast_to(feature_mask, (2 * W, F))
                if use_ic:
                    child_path = s["leaf_path"][sel_leaves] | \
                        (jnp.arange(F, dtype=jnp.int32)[None, :] ==
                         feat[:, None])                          # (W, F)
                    path2 = jnp.concatenate([child_path, child_path])
                    fm2 = fm2 & jax.vmap(allowed_features)(path2)
                ids2 = jnp.concatenate([2 * node_ids, 2 * node_ids + 1])
                if use_bynode:
                    fm2 = fm2 & node_mask_many(ids2)
                rb2 = node_rand_many(ids2) if use_et else None
                cegb2 = None
                if use_lazy:
                    # 1) mark the wave's split features as computed for every
                    # parent row (the reference marks the split leaf's rows,
                    # cost_effective_gradient_boosting.hpp:111-121) BEFORE the
                    # children scans, which must see the updated bitmap
                    used_b = s["used"]
                    slz = sel_leaves.astype(rl_old.dtype)
                    in_bag = bag_mask > 0
                    for j in range(W):
                        # only in-bag rows: the reference marks via the
                        # bagged DataPartition's GetIndexOnLeaf
                        m = sel[j] & (rl_old == slz[j]) & in_bag
                        used_b = used_b.at[feat[j]].set(
                            used_b[feat[j]] | (_pack_bits(m) if lp
                                               else m))
                    # 2) per-(feature, child) unused counts: grouped matvecs
                    # against the bitmap (0/1 bf16 products, f32 accumulation
                    # — exact to 2^24 counted rows per shard)
                    live2 = jnp.concatenate([sel, sel])
                    cid2 = jnp.where(live2, jnp.concatenate(
                        [sel_leaves, new_ids]), -2)
                    pad_c = (-cid2.shape[0]) % 7
                    if pad_c:
                        cid2 = jnp.concatenate(
                            [cid2, jnp.full((pad_c,), -2, cid2.dtype)])
                    used_f = (_unpack_bits(used_b) if lp
                              else used_b).astype(jnp.bfloat16)
                    # out-of-bag rows are invisible to the counts (sums2
                    # totals are bagged counts too)
                    rl32 = jnp.where(in_bag, rl.astype(jnp.int32), -9)

                    def cnt_group(cids):
                        m = (rl32[None, :] == cids[:, None]).astype(
                            jnp.bfloat16)                         # (7, N)
                        return jax.lax.dot_general(
                            used_f, m, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (F, 7)

                    used_cnt = jax.lax.map(cnt_group, cid2.reshape(-1, 7))
                    used_cnt = jnp.moveaxis(used_cnt, 0, 1).reshape(
                        F, -1)[:, :2 * W]                         # (F, 2W)
                    used_cnt = strat.reduce_sum(used_cnt)
                    unused = jnp.maximum(sums2[:, 2][None, :] - used_cnt, 0.0)
                    base = cegb_penalty if sp.use_cegb else \
                        jnp.zeros((F,), jnp.float32)
                    cegb2 = base[None, :] + (lazy_pen[:, None] * unused).T
                cands = many_candidates(ex2, sums2, bounds2, depth2, lv2, fm2,
                                        rb2, cegb2)
                depth_ok = jnp.logical_or(max_depth <= 0, child_depth < max_depth)
                dok2 = jnp.concatenate([depth_ok, depth_ok])
                cg = jnp.where(dok2 & jnp.concatenate([sel, sel]), cands[0],
                               NEG_INF)

            # ---- scatter state updates (invalid lanes -> dropped) ----
            with jax.named_scope("lgbm.wave.commit"):
                idx_l = jnp.where(sel, sel_leaves, L)
                idx_r = jnp.where(sel, new_ids, L)
                idx2 = jnp.concatenate([idx_l, idx_r])

                def sc2(arr, val2):
                    return arr.at[idx2].set(val2, mode="drop")

                out = dict(s)
                out["row_leaf"] = rl
                out["hists"] = s["hists"].at[idx_l].set(
                    hist_l, mode="drop").at[idx_r].set(hist_r, mode="drop")
                out["leaf_sum"] = sc2(s["leaf_sum"], sums2)
                out["leaf_depth"] = sc2(s["leaf_depth"], depth2)
                out["cand_gain"] = sc2(s["cand_gain"], cg)
                out["cand_feat"] = sc2(s["cand_feat"], cands[1])
                out["cand_bin"] = sc2(s["cand_bin"], cands[2])
                out["cand_dleft"] = sc2(s["cand_dleft"], cands[3])
                out["cand_lsum"] = sc2(s["cand_lsum"], cands[4])
                out["cand_rsum"] = sc2(s["cand_rsum"], cands[5])
                out["cand_member"] = sc2(s["cand_member"], cands[6])
                if use_mc and mc_inter:
                    # the sequential refinement already wrote child entries
                    # AND propagated caps to contiguous leaves
                    out["leaf_mn"] = mn_all
                    out["leaf_mx"] = mx_all
                    out["leaf_lo"] = lo_all
                    out["leaf_hi"] = hi_all
                elif use_mc:
                    out["leaf_mn"] = sc2(s["leaf_mn"],
                                         jnp.concatenate([mn_l, mn_r]))
                    out["leaf_mx"] = sc2(s["leaf_mx"],
                                         jnp.concatenate([mx_l, mx_r]))
                if use_ic:
                    out["leaf_path"] = sc2(s["leaf_path"], path2)
                if use_lazy:
                    out["used"] = used_b
                out["leaf_value"] = sc2(s["leaf_value"], lv2)
                out["leaf_weight"] = sc2(s["leaf_weight"], sums2[:, 1])
                out["leaf_count"] = sc2(s["leaf_count"], sums2[:, 2])

                # ---- tree node records ----
                nidx = jnp.where(sel, node_ids, L - 1)
                dleft_rec = jnp.where(fcat, member[:, 0], dleft)
                dt_bits = (jnp.where(fcat, CAT_MASK, 0) |
                           jnp.where(dleft_rec, DEFAULT_LEFT_MASK, 0) |
                           jnp.where(fnan & jnp.logical_not(fcat), MISSING_NAN, 0)
                           ).astype(jnp.int32)

                def scn(arr, val):
                    return arr.at[nidx].set(val, mode="drop")

                out["split_feature"] = scn(s["split_feature"], feat)
                out["threshold_bin"] = scn(s["threshold_bin"], thr)
                out["nan_bin"] = scn(s["nan_bin"], f_nan_bin)
                out["cat_member"] = scn(s["cat_member"], member)
                out["decision_type"] = scn(s["decision_type"], dt_bits)
                out["split_gain"] = scn(s["split_gain"], vals)
                out["internal_value"] = scn(
                    s["internal_value"], leaf_output(psum_[:, 0], psum_[:, 1], sp))
                out["internal_weight"] = scn(s["internal_weight"], psum_[:, 1])
                out["internal_count"] = scn(s["internal_count"], psum_[:, 2])

                # patch parent nodes' child slots pointing at the split leaves
                # (encoded as -(leaf+1)), then write the new nodes' own slots
                enc = -(sel_leaves + 1)
                for name in ("left_child", "right_child"):
                    arr = s[name]
                    match = (arr[:, None] == enc[None, :]) & sel[None, :]
                    has = jnp.any(match, axis=1)
                    pick = jnp.argmax(match, axis=1)
                    arr = jnp.where(has, node_ids[pick], arr)
                    if name == "left_child":
                        arr = arr.at[nidx].set(enc, mode="drop")
                    else:
                        arr = arr.at[nidx].set(-(new_ids + 1), mode="drop")
                    out[name] = arr

                out["num_leaves"] = nl0 + total_new
                out["done"] = total_new == 0
                out["pass_log"] = log_pass(s, PASS_WAVE, total_new, counts)
                out["hist_passes"] = s["hist_passes"] + 1
            return out

        if use_endgame:
            # ---- exact device-side endgame --------------------------
            # The main loop stops once the remaining budget drops below
            # 2W (instead of tapering the wave); the endgame below then
            # commits the rest in the TRUE sequential best-first order.
            # One batched kernel pass precomputes the smaller child of
            # each of the top-W frontier candidates (channel j = slot j's
            # smaller side, via the TRIAL form of the row-update kernel —
            # nothing committed); the selection while-loop then takes the
            # global top-1, writes its node records, derives BOTH
            # children's histograms from the cached bank by subtraction,
            # rescans the two children so they compete, and repeats.
            # Children born in the endgame have no precomputed bank entry
            # for their own candidates' children — when such a leaf
            # becomes the global best, the outer loop flushes the
            # committed row updates and runs ONE more batched pass over
            # the then-current frontier.  Every outer pass commits at
            # least one split (the global best always holds slot 0 of a
            # fresh pass), so the loop terminates; in the flattening-gain
            # endgame typical of deep trees one pass serves the whole
            # remaining budget, vs the taper's 3-4 full passes.
            EG = 2 * W   # pending-commit capacity (budget < 2W at entry)

            def _pend0():
                z = jnp.zeros((EG,), jnp.int32)
                return {"feat": z, "thr": z, "nan": z - 1, "dleft": z,
                        "leaf": z, "newid": z, "act": z}

            def _apply_pending(rl, pend, pcnt):
                """Flush committed endgame splits into row_leaf, in
                commit order (a row rerouted by an earlier entry can be
                caught by a later one — parents precede children)."""
                def flush(rl):
                    if pallas:
                        for c in range(EG // W):
                            sl = slice(c * W, (c + 1) * W)
                            tab = jnp.stack([
                                pend["thr"][sl], pend["nan"][sl],
                                pend["dleft"][sl],
                                jnp.zeros((W,), jnp.int32),
                                pend["leaf"][sl], pend["newid"][sl],
                                pend["act"][sl],
                                jnp.zeros((W,), jnp.int32)])
                            rl2, _ = route_rows(X_R, pend["feat"][sl],
                                                rl, tab)
                            rl = rl2.astype(rl_dtype)
                        return rl

                    def one(k, rl_):
                        colv = feature_col(pend["feat"][k]).astype(
                            jnp.int32)
                        go = jnp.where(colv == pend["nan"][k],
                                       pend["dleft"][k] > 0,
                                       colv <= pend["thr"][k])
                        move = ((pend["act"][k] > 0) &
                                (rl_ == pend["leaf"][k].astype(rl_.dtype))
                                & jnp.logical_not(go))
                        return jnp.where(
                            move, pend["newid"][k].astype(rl_.dtype), rl_)
                    return jax.lax.fori_loop(0, EG, one, rl)
                with jax.named_scope("lgbm.endgame.row_update"):
                    return jax.lax.cond(pcnt > 0, flush, lambda r: r, rl)

            def _trial_channels(rl, sel, sel_leaves, feat, thr, fnanb,
                                dleft, small):
                """(N,) int8 candidate slot whose SMALLER side each row
                would take (-1 = none) — the splits stay uncommitted."""
                if pallas:
                    bins, feats = router_args(X_R, feat)
                    return wave_trial_channels_pallas(
                        bins, rl, sel_leaves, thr, fnanb, dleft, small,
                        sel, feats=feats, interpret=interpret,
                        pipeline=pipeline)
                cols = jax.vmap(feature_col)(feat).astype(jnp.int32)
                go = jnp.where(cols == fnanb[:, None], dleft[:, None],
                               cols <= thr[:, None])
                match = sel[:, None] & \
                    (rl[None, :] == sel_leaves.astype(rl.dtype)[:, None])
                has = jnp.any(match, axis=0)
                jhit = jnp.argmax(match, axis=0)
                go_hit = jnp.take_along_axis(go, jhit[None, :], axis=0)[0]
                return jnp.where(has & (go_hit == small[jhit]),
                                 jhit.astype(jnp.int8), jnp.int8(-1))

            def _commit_cond(c):
                s, slot, pend, pcnt = c
                b = jnp.argmax(s["cand_gain"])
                return ((s["num_leaves"] < L) & (s["cand_gain"][b] > 0) &
                        (slot[b] >= 0))

            def _make_commit(bank):
                def _commit(c):
                    s, slot, pend, pcnt = c
                    b = jnp.argmax(s["cand_gain"]).astype(jnp.int32)
                    gain = s["cand_gain"][b]
                    feat = s["cand_feat"][b]
                    thr = s["cand_bin"][b]
                    dleft = s["cand_dleft"][b]
                    lsum = s["cand_lsum"][b]
                    rsum = s["cand_rsum"][b]
                    psum_ = s["leaf_sum"][b]
                    nl0 = s["num_leaves"]
                    new_id = nl0
                    node = nl0 - 1
                    fnan = hn_full[feat]
                    f_nan_bin = jnp.where(fnan, nb_full[feat] - 1, -1)
                    left_smaller = lsum[2] <= rsum[2]
                    hist_small = bank[slot[b]]
                    hist_big = histogram_subtract(s["hists"][b], hist_small)
                    hist_l = jnp.where(left_smaller, hist_small, hist_big)
                    hist_r = jnp.where(left_smaller, hist_big, hist_small)
                    # both children's candidates in one vmapped scan
                    child_depth = s["leaf_depth"][b] + 1
                    parent_lv = s["leaf_value"][b]
                    out_l = _child_out(lsum[0], lsum[1], lsum[2], parent_lv)
                    out_r = _child_out(rsum[0], rsum[1], rsum[2], parent_lv)
                    hists2 = jnp.stack([hist_l, hist_r])
                    sums2 = jnp.stack([lsum, rsum])
                    lv2 = jnp.stack([out_l, out_r])
                    d2 = jnp.full((2,), child_depth, jnp.int32)
                    cnds = many_candidates(
                        _scan_hists(hists2, sums2), sums2,
                        jnp.zeros((2, 2), jnp.float32), d2, lv2,
                        jnp.broadcast_to(feature_mask, (2, F)))
                    depth_ok = jnp.logical_or(max_depth <= 0,
                                              child_depth < max_depth)
                    cg2 = jnp.where(depth_ok, cnds[0], NEG_INF)
                    out = dict(s)
                    idx2 = jnp.stack([b, new_id])

                    def sc2(arr, val2):
                        return arr.at[idx2].set(val2)

                    out["hists"] = s["hists"].at[b].set(hist_l) \
                                             .at[new_id].set(hist_r)
                    out["leaf_sum"] = sc2(s["leaf_sum"], sums2)
                    out["leaf_depth"] = sc2(s["leaf_depth"], d2)
                    out["cand_gain"] = sc2(s["cand_gain"], cg2)
                    out["cand_feat"] = sc2(s["cand_feat"], cnds[1])
                    out["cand_bin"] = sc2(s["cand_bin"], cnds[2])
                    out["cand_dleft"] = sc2(s["cand_dleft"], cnds[3])
                    out["cand_lsum"] = sc2(s["cand_lsum"], cnds[4])
                    out["cand_rsum"] = sc2(s["cand_rsum"], cnds[5])
                    out["cand_member"] = sc2(s["cand_member"], cnds[6])
                    out["leaf_value"] = sc2(s["leaf_value"], lv2)
                    out["leaf_weight"] = sc2(s["leaf_weight"], sums2[:, 1])
                    out["leaf_count"] = sc2(s["leaf_count"], sums2[:, 2])
                    # node records via the shared sequential selector
                    dt_bits = (jnp.where(dleft, DEFAULT_LEFT_MASK, 0) |
                               jnp.where(fnan, MISSING_NAN, 0)
                               ).astype(jnp.int32)
                    lc, rc = patch_child_pointers(
                        s["left_child"], s["right_child"], b, node)
                    write_split_records(
                        out, node=node, leaf=b, new_id=new_id, feat=feat,
                        thr=thr, f_nan_bin=f_nan_bin, dt_bits=dt_bits,
                        gain=gain,
                        internal_value=leaf_output(psum_[0], psum_[1], sp),
                        internal_weight=psum_[1], internal_count=psum_[2],
                        left_child=lc, right_child=rc)
                    out["num_leaves"] = nl0 + 1
                    slot2 = slot.at[b].set(-1).at[new_id].set(-1)
                    pend2 = dict(pend)
                    for k_, v_ in (("feat", feat), ("thr", thr),
                                   ("nan", f_nan_bin),
                                   ("dleft", dleft.astype(jnp.int32)),
                                   ("leaf", b), ("newid", new_id),
                                   ("act", jnp.asarray(1, jnp.int32))):
                        pend2[k_] = pend2[k_].at[pcnt].set(v_)
                    return (out, slot2, pend2, pcnt + 1)
                return _commit

            def _eg_cond(c):
                s, pend, pcnt = c
                return (s["num_leaves"] < L) & \
                    (jnp.max(s["cand_gain"]) > 0)

            def _eg_body(c):
                s, pend, pcnt = c
                rl = _apply_pending(s["row_leaf"], pend, pcnt)
                s = dict(s)
                s["row_leaf"] = rl
                pend = _pend0()
                pcnt = jnp.asarray(0, jnp.int32)
                vals, sel_leaves = jax.lax.top_k(s["cand_gain"], W)
                sel = vals > 0
                feat = s["cand_feat"][sel_leaves]
                thr = s["cand_bin"][sel_leaves]
                dleft = s["cand_dleft"][sel_leaves]
                lsum = s["cand_lsum"][sel_leaves]
                rsum = s["cand_rsum"][sel_leaves]
                fnanb = jnp.where(hn_full[feat], nb_full[feat] - 1, -1)
                small = lsum[:, 2] <= rsum[:, 2]
                with jax.named_scope("lgbm.endgame.row_update"):
                    ch = _trial_channels(rl, sel, sel_leaves, feat, thr,
                                         fnanb, dleft, small)
                with jax.named_scope("lgbm.endgame.hist"):
                    # (W, G, Bb, 3); DP: one psum
                    bank, counts = hist_waves(ch, sparse=True)
                slot = jnp.full((L,), -1, jnp.int32).at[
                    jnp.where(sel, sel_leaves, L)].set(
                        jnp.arange(W, dtype=jnp.int32), mode="drop")
                with jax.named_scope("lgbm.endgame.select"):
                    s, slot, pend, pcnt = jax.lax.while_loop(
                        _commit_cond, _make_commit(bank),
                        (s, slot, pend, pcnt))
                s = dict(s)
                s["pass_log"] = log_pass(
                    s, PASS_ENDGAME, jnp.sum(sel, dtype=jnp.int32), counts)
                s["hist_passes"] = s["hist_passes"] + 1
                return (s, pend, pcnt)

        def cond(s):
            go = jnp.logical_not(s["done"]) & (s["num_leaves"] < L)
            if use_endgame:
                # hand off to the endgame instead of tapering the wave
                go = go & (s["num_leaves"] + 2 * W <= L)
            return go

        # pass kinds, read off the counters the loops already carry (no
        # new loop state): 1 + wave_passes + endgame_passes == hist_passes
        ramp_committed = state["num_leaves"] - 1 if use_spec \
            else jnp.asarray(0, jnp.int32)
        for fw in forced_waves:   # pre-committed ForceSplits prefix
            state = body(state, forced=fw)
        s = jax.lax.while_loop(cond, body, state)
        wave_passes = s["hist_passes"] - 1
        if use_endgame:
            with jax.named_scope("lgbm.endgame"):
                s, pend, pcnt = jax.lax.while_loop(
                    _eg_cond, _eg_body,
                    (s, _pend0(), jnp.asarray(0, jnp.int32)))
                s = dict(s)
                s["row_leaf"] = _apply_pending(s["row_leaf"], pend, pcnt)
            s["done"] = jnp.asarray(True)

        if quantized and renew_leaf:
            # Exact leaf-value renewal (the reference's
            # quant_train_renew_leaf, gbdt.cpp RenewTreeOutput analog):
            # one cheap exact pass replaces the quantized leaf sums with
            # true f32 gradient/hessian sums before outputs are committed.
            # On the Pallas path this reuses the single-leaf histogram
            # kernel with row_leaf as a one-feature bin column (cost
            # ~1/F of a wave pass); off-TPU it is a segment-sum.
            with jax.named_scope("lgbm.renew"):
                rl = s["row_leaf"].astype(jnp.int32)
                if pallas:
                    parts = []
                    for c in range((L + 255) // 256):
                        m = bag_mask * (rl // 256 == c).astype(bag_mask.dtype)
                        bins1 = (rl % 256).astype(jnp.uint8)[None, :]
                        parts.append(build_histogram_pallas(
                            bins1, grad, hess, m, num_bins=256,
                            interpret=interpret, kr=4096,
                            pipeline=pipeline)[0])
                    gh = jnp.concatenate(parts, axis=0)[:L, :2]       # (L, 2)
                else:
                    gh = jax.ops.segment_sum(
                        jnp.stack([gm, hm], axis=-1), rl, num_segments=L)
                gh = strat.reduce_sum(gh)
                vals = leaf_output(gh[:, 0], gh[:, 1], sp)
                if use_sm:
                    # path-smoothed outputs blend with the parent chain; renew
                    # against the recorded (pre-renew) value as the parent
                    # proxy — matches the reference's renew-in-place behavior
                    vals = leaf_output_smoothed(gh[:, 0], gh[:, 1],
                                                s["leaf_count"],
                                                s["leaf_value"], sp)
                if use_mc:
                    vals = jnp.clip(vals, s["leaf_mn"], s["leaf_mx"])
                live = jnp.arange(L, dtype=jnp.int32) < s["num_leaves"]
                ok = live & (s["leaf_count"] > 0)
                s["leaf_value"] = jnp.where(ok, vals, s["leaf_value"])
                s["leaf_weight"] = jnp.where(ok, gh[:, 1], s["leaf_weight"])

        leaf_count = s["leaf_count"]
        if wide and not (use_scatter or use_voting):
            # The state's counts went through float32 (the scan's sums),
            # exact to 2^24 rows; a data set that needs limbs has leaves
            # past that.  Every leaf's integer histogram is in the bank
            # (the root's, then both children's of every split), and the
            # bins of its first column hold each of its rows once: exact
            # int32 counts, at no pass over the rows.
            live = jnp.arange(L, dtype=jnp.int32) < s["num_leaves"]
            leaf_count = jnp.where(
                live, s["hists"][:, 0, :, 2].sum(axis=1), 0)

        tree_out = GrownTree(
            split_feature=s["split_feature"],
            threshold_bin=s["threshold_bin"],
            nan_bin=s["nan_bin"], cat_member=s["cat_member"],
            decision_type=s["decision_type"],
            left_child=s["left_child"], right_child=s["right_child"],
            split_gain=s["split_gain"], internal_value=s["internal_value"],
            internal_weight=s["internal_weight"],
            internal_count=s["internal_count"], leaf_value=s["leaf_value"],
            leaf_weight=s["leaf_weight"], leaf_count=leaf_count,
            num_leaves=s["num_leaves"],
            row_leaf=s["row_leaf"].astype(jnp.int32),
            hist_passes=s["hist_passes"], wave_passes=wave_passes,
            endgame_passes=s["hist_passes"] - 1 - wave_passes,
            ramp_committed=ramp_committed,
            # the rows the kernels looped over are the log's, summed
            hist_rows_contracted=jnp.stack(
                [s["pass_log"][:, 2].sum(),
                 jnp.asarray(row_unit, jnp.int32)])[None],
            pass_log=s["pass_log"][None], ramp_sample=ramp_sample[None])
        if use_lazy:
            return tree_out, s["used"]
        return tree_out

    fn = jax.jit(grow) if jit else grow
    # the grower's own statement of the static paths it was built with
    # (TrainRecord.snapshot()["grower"]): what a data set's shape switched
    # on or off, for a reader to hold a run to
    fn.static_paths = {
        "ramp": bool(use_spec), "endgame": bool(use_endgame),
        "scatter": bool(use_scatter), "voting": bool(use_voting),
        "efb": bool(use_efb), "any_cat": bool(any_cat),
        "sampled": bool(sampled and pallas),
        "row_update": "kernel" if fused_update else "xla",
        "hist_acc_rows": int(hist_acc_rows) if wide else 0}
    return fn
