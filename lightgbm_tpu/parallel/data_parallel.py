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

from ..config import Config
from ..learner.serial import (CommStrategy, GrownTree, WaveTreeLearner,
                              local_best_candidate, make_grow_fn,
                              hist_pool_fits, resolve_hist_impl,
                              resolve_monotone_method,
                              split_params_from_config)
from ..analysis.contracts import (collective_contract, memory_budget,
                                  world_size)
from ..telemetry.train_record import note_collective
from .mesh import get_mesh, shard_masked_grower

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


class DataParallelTreeLearner(WaveTreeLearner):
    """Host-side wrapper building the shard_map'd grower.

    Two growers: the WAVE grower (TPU default — leaf-batched histograms,
    one psum per wave, no row movement: ``WaveTreeLearner`` with a
    ``WaveDPStrategy``) and the masked sequential grower with per-split
    psum_scatter blocks (the reference DP layout,
    data_parallel_tree_learner.cpp:155-173; used off-TPU and when wave is
    gated off)."""

    name = "data"
    rows_sharded = True  # models/gbdt.py places per-row arrays on the mesh

    def __init__(self, config: Config, num_features: int, max_bins: int,
                 num_bins: np.ndarray, is_cat: np.ndarray, has_nan: np.ndarray,
                 monotone: Optional[np.ndarray] = None,
                 interaction_groups: tuple = (),
                 cegb_lazy: tuple = (), forced_splits: tuple = (),
                 acc_rows: int = 0):
        mesh = get_mesh(int(config.num_devices))
        mode = str(config.tree_grow_mode)
        impl_wave = resolve_hist_impl(config, parallel=True, wave=True,
                                      max_bins=int(max_bins))
        # same gates as SerialTreeLearner's wave_ok: the wave state carries
        # the full (L, G, B, 3) histogram pool — fall back to the masked
        # sequential grower when it would blow the HBM budget
        wave_able = (int(config.num_leaves) > 2 and
                     hist_pool_fits(config, num_features, int(max_bins)))
        if wave_able and (mode == "wave" or
                          (mode == "auto" and impl_wave == "pallas")):
            super().__init__(
                config, num_features, max_bins, num_bins, is_cat, has_nan,
                monotone, hist_impl=impl_wave,
                interaction_groups=interaction_groups, cegb_lazy=cegb_lazy,
                forced_splits=forced_splits, mesh=mesh, acc_rows=acc_rows,
                strategy=WaveDPStrategy(
                    mesh.axis_names[0], nshards=mesh.devices.size,
                    hist_scatter=bool(config.tpu_dp_hist_scatter)))
            return
        self.supports_extras = False
        if config.use_quantized_grad:
            from ..utils.log import log_warning
            log_warning("use_quantized_grad requires the wave grower; the "
                        "masked data-parallel grower trains with exact "
                        "gradients")
        if forced_splits:
            from ..utils.log import log_warning
            log_warning("forcedsplits_filename is applied by the DP-wave "
                        "grower only; the masked data-parallel grower "
                        "ignores it")
        sp = split_params_from_config(config, num_bins, is_cat)
        resolve_monotone_method(config, sp.use_monotone, wave=False)
        if interaction_groups or cegb_lazy or \
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
        self.f_pad = (-num_features) % mesh.devices.size
        fp = num_features + self.f_pad
        self.f_local = fp // mesh.devices.size
        mono_np = monotone if monotone is not None else np.zeros(num_features)
        self._describe(
            config, num_features, max_bins,
            np.concatenate([num_bins, np.ones(self.f_pad, np.int32)]),
            np.concatenate([is_cat, np.zeros(self.f_pad, bool)]),
            np.concatenate([has_nan, np.zeros(self.f_pad, bool)]),
            np.concatenate([mono_np, np.zeros(self.f_pad)]), mesh=mesh)
        strategy = DataParallelStrategy(self.axis, self.f_local,
                                        self.num_bins, self.is_cat,
                                        self.has_nan)
        self._grow = shard_masked_grower(make_grow_fn(
            num_leaves=int(config.num_leaves), max_bins=self.max_bins,
            max_depth=int(config.max_depth), split_params=sp,
            hist_impl=resolve_hist_impl(config, parallel=True),
            rows_per_chunk=int(config.tpu_rows_per_chunk),
            use_hist_pool=hist_pool_fits(config, fp, self.max_bins),
            strategy=strategy, jit=False), mesh, self.axis)

    def _train_other(self, X_dev, grad, hess, sample_mask, feature_mask,
                     cegb_penalty, node_key) -> GrownTree:
        n = X_dev.shape[0]
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
