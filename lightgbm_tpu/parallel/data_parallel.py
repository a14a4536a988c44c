"""Data-parallel tree learner: rows sharded over the mesh.

TPU-native re-implementation of the reference DataParallelTreeLearner
(reference: src/treelearner/data_parallel_tree_learner.cpp — rows partitioned
across machines, local histograms ReduceScatter'd so each machine reduces a
disjoint feature block :155-173 with the block layout computed at :58-124,
local best splits on owned features only :176-251, allreduce-max of the best
SplitInfo :244, global leaf counts via parallel_tree_learner.h:67).

Here the learner is the shared grower wrapped in ``shard_map`` over a 1-D
mesh: the binned matrix, gradients and row_leaf partition live row-sharded;
the per-leaf histogram pool keeps shard-LOCAL histograms (histogram
subtraction is linear, so local parent − local child = local sibling), and
each candidate search runs ``psum_scatter`` so every device reduces and
scans ONE disjoint feature block — per-device communication is F·B/ndev
instead of the F·B a full psum moves, exactly the reference's
reduce-scatter refinement.  The winning candidate is then combined with a
pmax + owner-broadcast (the SplitInfo allreduce-max analog); global leaf
counts fall out of the psum'd count channel (GetGlobalDataCountInLeaf).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..config import Config
from ..learner.serial import (CommStrategy, GrownTree, local_best_candidate,
                              make_grow_fn, hist_pool_fits, resolve_hist_impl,
                              split_params_from_config)
from ..analysis.contracts import (collective_contract, memory_budget,
                                  world_size)
from ..telemetry.trace import timed_span
from ..telemetry.train_record import note_collective
from .mesh import get_mesh, shard_rows

__all__ = ["DataParallelTreeLearner", "DataParallelStrategy"]

BIG_FEAT = np.int32(2 ** 30)


def _masked_scan_budget(ctx):
    """Masked-grower candidate scans per traced program: bounded by a
    small multiple of the static leaf budget (the while body traces
    once; root + two children per commit site)."""
    return 8 * max(2, int(ctx.get("leaves", 2)))


def _masked_hist_block_bytes(ctx):
    """psum_scatter operand: the full LOCAL (Fp, B, 3) histogram goes in,
    each shard receives its Fp/k block fully reduced (the reference's
    per-split ReduceScatter, data_parallel_tree_learner.cpp:155-173).
    ``k`` is the mesh world size so one declaration covers W=4..W=256."""
    k = world_size(ctx)
    f_pad = -(-int(ctx["features"]) // k) * k
    return f_pad * int(ctx["bins"]) * 3 * int(ctx.get("itemsize", 4))


# Contracts for the MASKED sequential DP grower's sites (the wave-path
# sites are declared next to their merge logic in learner/wave.py).
collective_contract("data_parallel/masked/leaf_sum", "psum",
                    max_count=_masked_scan_budget, max_bytes_per_op=256)
collective_contract("data_parallel/masked/hist_reduce_scatter",
                    "psum_scatter", max_count=_masked_scan_budget,
                    max_bytes_per_op=_masked_hist_block_bytes,
                    note="one reduce-scatter per candidate scan; "
                         "operand is the local histogram")
collective_contract("data_parallel/masked/best_gain", "pmax",
                    max_count=_masked_scan_budget, max_bytes_per_op=64)
collective_contract("data_parallel/masked/best_feature", "pmin",
                    max_count=_masked_scan_budget, max_bytes_per_op=64)
collective_contract("data_parallel/masked/winner_bcast", "psum",
                    max_count=lambda ctx: 8 * _masked_scan_budget(ctx),
                    max_bytes_per_op=lambda ctx: 4 * max(
                        64, int(ctx["bins"])),
                    note="winner payload incl. the (B,) cat membership")


# ---------------------------------------------------------------------------
# Memory budget for the sliced DP-wave program family (lint-mem
# enforced): the per-device working set on the reduce-scatter path.
# Two full-F local kernel banks (the pre-merge local histograms the
# quantized kernel builds at Q_WAVE_SIZE=42 channels) dominate; AFTER
# the merge everything is a ceil(F/k) feature slice — the per-leaf bank,
# the scan operands, the winner rescans.  An un-scattered merge (the
# planted regression class) re-inflates the post-merge terms to full F
# and blows through this curve.
# ---------------------------------------------------------------------------

def dp_sliced_hbm_bytes(ctx):
    """Per-device HBM curve of one sliced DP-wave tree program as a
    function of (rows, features, bins, wave_size, leaves, world_size)."""
    from ..learner.wave import Q_WAVE_SIZE, WAVE_SIZE
    k = world_size(ctx)
    f = int(ctx["features"])
    b = int(ctx["bins"])
    it = int(ctx.get("itemsize", 4))
    r = -(-int(ctx["rows"]) // k)
    wave = int(ctx.get("wave_size", WAVE_SIZE))
    kernel_ch = Q_WAVE_SIZE if ctx.get("quantized", True) else WAVE_SIZE
    # pre-merge: 2.5 local full-F channel banks in flight (build + merge)
    local_banks = int(2.5 * max(2 * wave, kernel_ch) * f * b * 3 * it)
    # post-merge: per-leaf bank + scan/rescan temporaries on the slice
    f_blk = -(-f // k)
    sliced = (int(ctx.get("leaves", 2)) + 6 * wave) * f_blk * b * 3 * it
    rows = r * (f + 24)
    return local_banks + sliced + rows + (1 << 20)


memory_budget(
    "data_parallel/wave_sliced", ("dp_scatter", "spec_ramp"),
    dp_sliced_hbm_bytes,
    note="2.5 local full-F kernel banks + F/k post-merge slice + rows")


class DataParallelStrategy(CommStrategy):
    rows_sharded = True
    """Local histograms + per-candidate psum_scatter over feature blocks
    (SURVEY.md §2.5 mapping; data_parallel_tree_learner.cpp:155-173)."""

    def __init__(self, axis_name, f_local, num_bins, is_cat, has_nan):
        super().__init__(num_bins, is_cat, has_nan)
        self.axis_name = axis_name
        self.f_local = f_local

    def reduce_sum(self, v):
        note_collective("data_parallel/masked/leaf_sum", "psum", v)
        return jax.lax.psum(v, self.axis_name)

    # reduce_hist stays identity: the pool keeps shard-LOCAL histograms;
    # cross-shard reduction happens inside leaf_candidates on disjoint
    # feature blocks (reduce-scatter), never on the full tensor.

    def leaf_candidates(self, hist_local, leaf_sum, feature_mask, params,
                        bound=None, depth=None, parent_out=None):
        fb = self.f_local
        r = jax.lax.axis_index(self.axis_name)
        start = r * fb
        # each device reduces + owns one contiguous feature block
        note_collective("data_parallel/masked/hist_reduce_scatter",
                        "psum_scatter", hist_local)
        blk = jax.lax.psum_scatter(hist_local, self.axis_name,
                                   scatter_dimension=0, tiled=True)
        sl = lambda a: jax.lax.dynamic_slice(a, (start,), (fb,))
        mono = sl(self.monotone_full) if self.monotone_full is not None \
            else None
        g, f_loc, b, dl, ls, rs, member = local_best_candidate(
            blk, leaf_sum, sl(self.num_bins_full), sl(self.is_cat_full),
            sl(self.has_nan_full), sl(feature_mask), params, mono, bound,
            depth, parent_out=parent_out)
        # allreduce-max of the per-block winners with deterministic
        # tie-break on the global feature index (SplitInfo ladder)
        note_collective("data_parallel/masked/best_gain", "pmax", g)
        gmax = jax.lax.pmax(g, self.axis_name)
        f_glob = start.astype(jnp.int32) + f_loc
        cand = jnp.where(g >= gmax, f_glob, BIG_FEAT)
        note_collective("data_parallel/masked/best_feature", "pmin", cand)
        f_win = jax.lax.pmin(cand, self.axis_name)
        is_win = (f_glob == f_win) & (g >= gmax)

        def bcast(v):
            note_collective("data_parallel/masked/winner_bcast", "psum", v)
            return jax.lax.psum(
                jnp.where(is_win, v, jnp.zeros_like(v)), self.axis_name)

        return (gmax, f_win, bcast(b), bcast(dl.astype(jnp.int32)) > 0,
                bcast(ls), bcast(rs), bcast(member.astype(jnp.int32)) > 0)

    def pair_candidates(self, hist_l, hist_r, lsum, rsum, feature_mask,
                        params, bound_l, bound_r, depth, fm_l=None,
                        fm_r=None, po_l=None, po_r=None):
        # collectives are not vmap-batched: two sequential candidate calls
        return (self.leaf_candidates(
                    hist_l, lsum,
                    feature_mask if fm_l is None else fm_l, params,
                    bound_l, depth, po_l),
                self.leaf_candidates(
                    hist_r, rsum,
                    feature_mask if fm_r is None else fm_r, params,
                    bound_r, depth, po_r))


class WaveDPStrategy(CommStrategy):
    """Row-sharded strategy for the wave grower: ONE histogram collective
    per wave (up to 25/42 splits' smaller children).

    Two merge modes for that collective:

    * ``hist_scatter=False`` — full-batch ``psum``: every shard holds the
      whole merged histogram and the candidate scans run replicated.
    * ``hist_scatter=True`` — feature-sliced ``psum_scatter`` (the
      reference DP learner's ReduceScatter refinement,
      data_parallel_tree_learner.cpp:155-173, amortized over the wave's
      channels): each shard materializes only its F/k feature block of
      the merged batch, scans that slice, and the per-leaf winners are
      combined by the wave grower's O(W*k) winner exchange
      (learner/wave.py).  1/k the wire residency and 1/k the scan FLOPs
      per pass; results identical to the psum mode.

    ``spec_ok``/``nshards`` unlock the speculative ramp on this path:
    each shard strides its local rows for the provisional subsample
    (global budget / nshards each) and the provisional passes reduce
    their histogram batches like committed waves — one extra collective
    per provisional pass, nothing else (learner/wave.py _spec_state)."""

    rows_sharded = True
    spec_ok = True

    def __init__(self, axis_name: str, nshards: int = 1,
                 hist_scatter: bool = False):
        self.axis_name = axis_name
        self.nshards = int(nshards)
        self.hist_scatter = bool(hist_scatter)
        self.monotone_full = None

    # Every collective sits in an innermost ``lgbm.dp.*`` scope, so a
    # device trace tells the exchange from the glue around it:
    # ``lgbm.dp.hist_reduce`` (the one histogram merge a pass makes),
    # ``lgbm.dp.exchange`` (the winner exchange), ``lgbm.dp.scalar``.

    def reduce_sum(self, v):
        note_collective("data_parallel/wave/scalar_sum", "psum", v)
        with jax.named_scope("lgbm.dp.scalar"):
            return jax.lax.psum(v, self.axis_name)

    def reduce_max(self, v):
        """Global quantization scales: every shard must see the same max
        (gradient_discretizer scales are global in the reference too)."""
        note_collective("data_parallel/wave/quant_scale", "pmax", v)
        with jax.named_scope("lgbm.dp.scalar"):
            return jax.lax.pmax(v, self.axis_name)

    def shard_key(self, key):
        """Independent stochastic-rounding streams per row shard."""
        return jax.random.fold_in(key, jax.lax.axis_index(self.axis_name))

    def reduce_hist(self, hist):
        # THE data-parallel collective: one histogram-batch psum per wave
        # / provisional pass (PERF.md's one-psum-per-pass contract,
        # asserted on the traced program in tests/test_specramp.py — this
        # tally counts the same sites at trace time)
        note_collective("data_parallel/wave/hist_psum", "psum", hist)
        with jax.named_scope("lgbm.dp.hist_reduce"):
            return jax.lax.psum(hist, self.axis_name)

    def reduce_hist_scatter(self, hist):
        """Feature-sliced merge: reduce-scatter the (k, Fp, B, 3) batch
        over the padded feature axis so this shard receives only its
        Fp/nshards block, fully reduced.  The telemetry note records the
        scattered OUTPUT as ``bytes`` (the per-device received payload —
        1/k of the psum mode's full-batch residency) and the local batch
        that goes in as ``operand_bytes``."""
        with jax.named_scope("lgbm.dp.hist_reduce"):
            out = jax.lax.psum_scatter(hist, self.axis_name,
                                       scatter_dimension=1, tiled=True)
        note_collective("data_parallel/wave/hist_reduce_scatter",
                        "psum_scatter", out, operand=hist)
        return out

    def exchange_collectives(self):
        """(pmax, pmin, psum) hooks of the wave grower's winner exchange,
        telemetry-tagged — the SplitInfo allreduce-max analog
        (data_parallel_tree_learner.cpp:244), O(W*k) bytes per scan."""
        ax = self.axis_name

        def xmax(v):
            note_collective("data_parallel/wave/winner_exchange", "pmax", v)
            with jax.named_scope("lgbm.dp.exchange"):
                return jax.lax.pmax(v, ax)

        def xmin(v):
            note_collective("data_parallel/wave/winner_exchange", "pmin", v)
            with jax.named_scope("lgbm.dp.exchange"):
                return jax.lax.pmin(v, ax)

        def xsum(v):
            note_collective("data_parallel/wave/winner_exchange", "psum", v)
            with jax.named_scope("lgbm.dp.exchange"):
                return jax.lax.psum(v, ax)

        return xmax, xmin, xsum


class DataParallelTreeLearner:
    """Host-side wrapper building the shard_map'd grower.

    Two growers: the WAVE grower (TPU default — leaf-batched histograms,
    one psum per wave, no row movement) and the masked sequential grower
    with per-split psum_scatter blocks (the reference DP layout,
    data_parallel_tree_learner.cpp:155-173; used off-TPU and when wave is
    gated off)."""

    name = "data"
    rows_sharded = True  # models/gbdt.py places per-row arrays on the mesh

    def __init__(self, config: Config, num_features: int, max_bins: int,
                 num_bins: np.ndarray, is_cat: np.ndarray, has_nan: np.ndarray,
                 monotone: Optional[np.ndarray] = None,
                 interaction_groups: tuple = (),
                 cegb_lazy: tuple = (), forced_splits: tuple = ()):
        self.config = config
        self.max_bins = int(max_bins)
        self.num_features = num_features
        self.interaction_groups = tuple(tuple(g) for g in interaction_groups)
        self.cegb_lazy = tuple(float(v) for v in cegb_lazy)
        self.forced_splits = tuple(tuple(f) for f in forced_splits)
        self.mesh = get_mesh(int(config.num_devices))
        self.setup_seconds = {}   # "layout": see train()
        self.ndev = self.mesh.devices.size
        self.axis = self.mesh.axis_names[0]
        mode = str(config.tree_grow_mode)
        impl_wave = resolve_hist_impl(config, parallel=True, wave=True,
                                      max_bins=self.max_bins)
        # same gates as SerialTreeLearner's wave_ok: the wave state carries
        # the full (L, G, B, 3) histogram pool — fall back to the masked
        # sequential grower when it would blow the HBM budget
        wave_able = (int(config.num_leaves) > 2 and
                     hist_pool_fits(config, num_features, self.max_bins))
        self.wave = wave_able and (mode == "wave" or
                                   (mode == "auto" and
                                    impl_wave == "pallas"))
        if self.wave:
            self._init_wave(config, num_features, num_bins, is_cat, has_nan,
                            monotone, impl_wave)
            return
        self.quantized = False
        self.supports_extras = False
        if config.use_quantized_grad:
            from ..utils.log import log_warning
            log_warning("use_quantized_grad requires the wave grower; the "
                        "masked data-parallel grower trains with exact "
                        "gradients")
        if self.forced_splits:
            from ..utils.log import log_warning
            log_warning("forcedsplits_filename is applied by the DP-wave "
                        "grower only; the masked data-parallel grower "
                        "ignores it")
        from ..learner.serial import (resolve_monotone_method,
                                      split_params_from_config as _spc)
        resolve_monotone_method(config, _spc(config, num_bins,
                                             is_cat).use_monotone,
                                wave=False)
        if self.interaction_groups or self.cegb_lazy or \
                config.extra_trees or \
                config.feature_fraction_bynode < 1.0 or \
                config.cegb_penalty_split > 0 or \
                config.cegb_penalty_feature_coupled:
            from ..utils.log import log_warning
            log_warning("extra_trees / bynode sampling / cegb / interaction"
                        " constraints under tree_learner=data require the "
                        "wave grower (tree_grow_mode=wave, or auto on TPU);"
                        " the masked DP grower ignores them")
        # pad the feature axis to a multiple of the mesh so psum_scatter
        # blocks are uniform (padded features are trivial: 1 bin, never
        # splittable — the analog of the reference's balanced block layout)
        self.f_pad = (-num_features) % self.ndev
        fp = num_features + self.f_pad
        self.f_local = fp // self.ndev
        self.num_bins = jnp.asarray(
            np.concatenate([num_bins, np.ones(self.f_pad, np.int32)]),
            jnp.int32)
        self.is_cat = jnp.asarray(
            np.concatenate([is_cat, np.zeros(self.f_pad, bool)]), jnp.bool_)
        self.has_nan = jnp.asarray(
            np.concatenate([has_nan, np.zeros(self.f_pad, bool)]), jnp.bool_)
        mono_np = monotone if monotone is not None else np.zeros(num_features)
        self.monotone = jnp.asarray(
            np.concatenate([mono_np, np.zeros(self.f_pad)]), jnp.int32)
        strategy = DataParallelStrategy(self.axis, self.f_local,
                                        self.num_bins, self.is_cat,
                                        self.has_nan)
        grow_t = make_grow_fn(
            num_leaves=int(config.num_leaves), max_bins=self.max_bins,
            max_depth=int(config.max_depth),
            split_params=split_params_from_config(config, num_bins, is_cat),
            hist_impl=resolve_hist_impl(config, parallel=True),
            rows_per_chunk=int(config.tpu_rows_per_chunk),
            use_hist_pool=hist_pool_fits(config, fp, self.max_bins),
            strategy=strategy, jit=False)

        def grow(X, g, h, m, nb, ic, hn, mono, fm):
            return grow_t(X, None, g, h, m, nb, ic, hn, mono, fm)
        tree_specs = self._tree_specs(self.axis)
        self._grow = jax.jit(jax.shard_map(
            grow, mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis), P(self.axis), P(self.axis),
                      P(), P(), P(), P(), P()),
            out_specs=tree_specs,
            check_vma=False))

    @staticmethod
    def _tree_specs(axis):
        return GrownTree(
            split_feature=P(), threshold_bin=P(), nan_bin=P(),
            cat_member=P(), decision_type=P(), left_child=P(),
            right_child=P(), split_gain=P(), internal_value=P(),
            internal_weight=P(), internal_count=P(), leaf_value=P(),
            leaf_weight=P(), leaf_count=P(), num_leaves=P(),
            row_leaf=P(axis), hist_passes=P(), wave_passes=P(),
            endgame_passes=P(), ramp_committed=P())

    def _init_wave(self, config, num_features, num_bins, is_cat, has_nan,
                   monotone, impl):
        from ..learner.wave import make_wave_grow_fn
        self.f_pad = 0
        self.pallas = impl == "pallas"
        self.num_bins = jnp.asarray(num_bins, jnp.int32)
        self.is_cat = jnp.asarray(is_cat, jnp.bool_)
        self.has_nan = jnp.asarray(has_nan, jnp.bool_)
        mono_np = monotone if monotone is not None else np.zeros(num_features)
        self.monotone = jnp.asarray(mono_np, jnp.int32)
        self._x_src = None
        self.supports_extras = True
        from ..ops.quantize import quant_levels
        self.quantized = bool(config.use_quantized_grad)
        sp = split_params_from_config(config, num_bins, is_cat)
        if np.any(np.asarray(is_cat)):
            # the DP-WAVE scan runs replicated in FULL feature space
            # (unlike the masked psum_scatter blocks) — attach the static
            # cat positions that bound the subset search's argsort
            sp = sp._replace(cat_idx=tuple(
                int(j) for j in np.where(np.asarray(is_cat))[0]))
        self.split_params = sp
        from ..learner.serial import resolve_monotone_method
        mc_inter = resolve_monotone_method(config, sp.use_monotone,
                                           wave=True)
        self._use_node_key = sp.feature_fraction_bynode < 1.0 or \
            sp.extra_trees
        gq_max, hq_max = quant_levels(int(config.num_grad_quant_bins))
        strategy = WaveDPStrategy(
            self.axis, nshards=self.ndev,
            hist_scatter=bool(config.tpu_dp_hist_scatter))
        grow_w = make_wave_grow_fn(
            num_leaves=int(config.num_leaves), num_features=num_features,
            max_bins=self.max_bins, max_depth=int(config.max_depth),
            split_params=sp,
            hist_impl=impl, any_cat=bool(np.any(np.asarray(is_cat))),
            wave_size=int(config.tpu_wave_size), strategy=strategy,
            jit=False, quantized=self.quantized, gq_max=gq_max,
            hq_max=hq_max,
            renew_leaf=bool(config.quant_train_renew_leaf),
            stochastic=bool(config.stochastic_rounding),
            interaction_groups=self.interaction_groups,
            cegb_lazy=self.cegb_lazy, forced_splits=self.forced_splits,
            mc_inter=mc_inter,
            spec_ramp=bool(config.tpu_speculative_ramp),
            spec_tol=float(config.tpu_spec_tolerance),
            exact_endgame=bool(config.tpu_exact_endgame))

        # cegb penalties, the quantization/bynode keys and the persistent
        # lazy-CEGB bitmap ride extra operands; arity is static config
        nq = int(self.quantized)
        nn = int(self._use_node_key)
        nl = int(bool(self.cegb_lazy))

        def grow(X_T, g, h, m, nb, ic, hn, mono, fm, cegb, *rest):
            kw = {}
            ki = 0
            if nq:
                kw["quant_key"] = rest[ki]
                ki += 1
            if nn:
                kw["node_key"] = rest[ki]
                ki += 1
            if nl:
                kw["lazy_used"] = rest[ki]
            return grow_w(X_T, g, h, m, nb, ic, hn, mono, cegb, (), fm,
                          **kw)

        tree_specs = self._tree_specs(self.axis)
        out_specs = (tree_specs, P(None, self.axis)) if nl else tree_specs
        self._grow = jax.jit(jax.shard_map(
            grow, mesh=self.mesh,
            in_specs=(P(None, self.axis), P(self.axis), P(self.axis),
                      P(self.axis), P(), P(), P(), P(), P(), P()) +
            (P(),) * (nq + nn) +
            ((P(None, self.axis),) if nl else ()),
            out_specs=out_specs,
            check_vma=False))
        self._lazy_used = None

    def train(self, X_dev: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
              sample_mask: jnp.ndarray,
              feature_mask: Optional[jnp.ndarray] = None,
              quant_key: Optional[jnp.ndarray] = None,
              cegb_penalty: Optional[jnp.ndarray] = None,
              node_key: Optional[jnp.ndarray] = None) -> GrownTree:
        if feature_mask is None:
            feature_mask = jnp.ones((self.num_features,), jnp.bool_)
        n = X_dev.shape[0]
        if self.wave:
            # each shard's rows must satisfy the Pallas row-block contract
            if self.pallas:
                from ..ops.histogram_pallas import DEFAULT_ROW_BLOCK
                quantum = self.ndev * DEFAULT_ROW_BLOCK
            else:
                # x8 so each shard's rows (and the packed lazy-CEGB
                # bitmap's byte columns) stay 8-divisible
                quantum = self.ndev * 8
            pad = (-n) % quantum
            if self._x_src is not X_dev:
                # one-time pad + transpose; host seconds of ENQUEUEING it
                with timed_span(self.setup_seconds, "layout", "train/layout"):
                    Xp = jnp.pad(X_dev, ((0, pad), (0, 0))) if pad else X_dev
                    self._XpT = shard_rows(self.mesh, jnp.swapaxes(Xp, 0, 1),
                                           self.axis, dim=1)
                self._x_src = X_dev
                self._lazy_used = None  # fresh data -> fresh bitmap
            if pad:
                grad = jnp.pad(grad, (0, pad))
                hess = jnp.pad(hess, (0, pad))
                sample_mask = jnp.pad(sample_mask, (0, pad))
            grad, hess, sample_mask = (
                shard_rows(self.mesh, v, self.axis)
                for v in (grad, hess, sample_mask))
            if cegb_penalty is None:
                cegb_penalty = jnp.zeros((self.num_features,), jnp.float32)
            keys = []
            if self.quantized:
                if quant_key is None:
                    self._quant_calls = getattr(self, "_quant_calls", 0) + 1
                    quant_key = jax.random.PRNGKey(self._quant_calls)
                keys.append(quant_key)
            if self._use_node_key:
                if node_key is None:
                    node_key = jnp.zeros((2, 2), jnp.uint32)
                keys.append(node_key)
            if self.cegb_lazy:
                from ..learner.wave import LAZY_PACK, lazy_bitmap_init
                n_pad_all = self._XpT.shape[1]
                if self._lazy_used is None or \
                        self._lazy_used.shape[1] != n_pad_all // LAZY_PACK:
                    self._lazy_used = lazy_bitmap_init(
                        self.num_features, n_pad_all)
                keys.append(self._lazy_used)
            out = self._grow(self._XpT, grad, hess, sample_mask,
                             self.num_bins, self.is_cat, self.has_nan,
                             self.monotone, feature_mask, cegb_penalty,
                             *keys)
            if self.cegb_lazy:
                grown, self._lazy_used = out
            else:
                grown = out
            if pad:
                grown = grown._replace(row_leaf=grown.row_leaf[:n])
            return grown
        if self.f_pad:
            X_dev = jnp.pad(X_dev, ((0, 0), (0, self.f_pad)))
            feature_mask = jnp.pad(feature_mask, (0, self.f_pad))
        pad = (-n) % self.ndev
        if pad:
            X_dev = jnp.pad(X_dev, ((0, pad), (0, 0)))
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
            sample_mask = jnp.pad(sample_mask, (0, pad))
        grown = self._grow(X_dev, grad, hess, sample_mask, self.num_bins,
                           self.is_cat, self.has_nan, self.monotone,
                           feature_mask)
        if pad:
            grown = grown._replace(row_leaf=grown.row_leaf[:n])
        return grown
