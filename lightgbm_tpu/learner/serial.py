"""Leaf-wise tree grower + serial (single-device) learner.

TPU-native re-implementation of the reference SerialTreeLearner
(reference: src/treelearner/serial_tree_learner.cpp:158 ``Train`` — best-first
growth to num_leaves with per-leaf histograms, the histogram subtraction trick
at :311-320, split finding at :374, partition update at :564).

Design (SURVEY.md §7): the whole tree grows inside ONE jitted function with a
``lax.fori_loop`` over the num_leaves-1 splits — no host round-trips per
split.  Static shapes throughout:

* leaf membership is a per-row ``row_leaf`` int32 vector (replaces the
  reference's DataPartition index shuffling, data_partition.hpp:170) — the
  partition update after a split is a masked ``where``;
* per-leaf histograms live in a (num_leaves, F, B, 3) pool when it fits the
  memory budget, enabling the parent-minus-sibling subtraction trick; with
  many features the learner switches to recompute mode (two masked passes per
  split, no pool) — the analog of the reference's bounded HistogramPool
  (feature_histogram.hpp:1095);
* split finding is the vectorized bin scan in ops/split.py;
* the best-leaf argmax replaces serial_tree_learner.cpp:194's ArgMax over
  best_split_per_leaf_.

After a split, the left child keeps the parent's leaf id and the right child
takes the next fresh id (matching the reference Tree::Split leaf numbering).

The grower is parameterized by a **communication strategy** — the TPU analog
of the reference templating its parallel learners over the device learner
(parallel_tree_learner.h:54 ``DataParallelTreeLearner<TREELEARNER_T>``):
the serial strategy is all-identity; data-/feature-/voting-parallel
strategies (lightgbm_tpu/parallel/) insert ``jax.lax`` collectives at the
same points the reference calls its Network layer.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..ops.histogram import build_histogram
from ..ops.split import (BIG, NEG_INF, FeatureSplits, SplitParams,
                         best_split_per_feature, leaf_output)
from ..models.tree import CAT_MASK, DEFAULT_LEFT_MASK, MISSING_NAN
from ..telemetry.trace import timed_span

__all__ = ["SerialTreeLearner", "GrownTree", "make_grow_fn", "CommStrategy",
           "local_best_candidate", "untracked_passes"]


class GrownTree(NamedTuple):
    """Device-side result of growing one tree."""
    split_feature: jnp.ndarray     # (L-1,) int32 (global feature indices)
    threshold_bin: jnp.ndarray     # (L-1,) int32
    nan_bin: jnp.ndarray           # (L-1,) int32
    cat_member: jnp.ndarray        # (L-1, B) bool — categorical LEFT bins
    decision_type: jnp.ndarray     # (L-1,) int32
    left_child: jnp.ndarray        # (L-1,) int32
    right_child: jnp.ndarray       # (L-1,) int32
    split_gain: jnp.ndarray        # (L-1,) float32
    internal_value: jnp.ndarray    # (L-1,) float32
    internal_weight: jnp.ndarray   # (L-1,) float32
    internal_count: jnp.ndarray    # (L-1,) float32
    leaf_value: jnp.ndarray        # (L,) float32
    leaf_weight: jnp.ndarray       # (L,) float32
    leaf_count: jnp.ndarray        # (L,) float32 (int32, exact past 2^24
                                   # rows, from a grower that keeps limbs)
    num_leaves: jnp.ndarray        # () int32 — actual leaves grown
    row_leaf: jnp.ndarray          # (N,) int32 — final leaf of every row
    hist_passes: jnp.ndarray       # () int32 — full-data histogram passes
    #                                spent growing this tree (wave grower;
    #                                0 = untracked: the partitioned/masked
    #                                growers' per-split builds scale with
    #                                the split leaf's size, not with N)
    # the kinds of those passes (wave grower; 0 elsewhere):
    # 1 + wave_passes + endgame_passes == hist_passes
    wave_passes: jnp.ndarray       # () int32 — committed waves
    endgame_passes: jnp.ndarray    # () int32 — exact-endgame bank passes
    ramp_committed: jnp.ndarray    # () int32 — splits the speculative
    #                                ramp's verifying pass committed, of
    #                                W-1 provisional (0 with the ramp off)
    hist_rows_contracted: jnp.ndarray  # (shards, 2) int32 — per row shard
    #                                [count, unit]: count * unit rows were
    #                                looped over by the histogram kernels of
    #                                those passes (hist_passes * N unless a
    #                                pass compacted its rows; 0 = untracked)
    pass_log: jnp.ndarray          # (shards, P, 6) int32 — per row shard,
    #                                one entry a counted pass in its order:
    #                                [kind (learner/wave.py PASS_*), leaves
    #                                built, rows looped (in the unit above),
    #                                lanes with a channel, compaction blocks,
    #                                blocks holding such a lane]; P = 0:
    #                                a grower that keeps no log
    ramp_sample: jnp.ndarray       # (shards, 2) int32 — [in-bag lanes,
    #                                lanes] of the speculative ramp's
    #                                subsample (0, 0 with the ramp off)


def untracked_passes() -> dict:
    """The pass-count fields of a grower that does not count passes."""
    z = jnp.asarray(0, jnp.int32)
    return dict(hist_passes=z, wave_passes=z, endgame_passes=z,
                ramp_committed=z,
                hist_rows_contracted=jnp.zeros((1, 2), jnp.int32),
                pass_log=jnp.zeros((1, 0, 6), jnp.int32),
                ramp_sample=jnp.zeros((1, 2), jnp.int32))


def local_best_candidate(hist, leaf_sum, num_bins, is_cat, has_nan,
                         feature_mask, params, monotone=None, bound=None,
                         depth=None, cegb=None, contri=None,
                         parent_out=None, rand_bins=None
                         ) -> Tuple[jnp.ndarray, ...]:
    """Best split over (local) features for one leaf -> scalar candidate
    tuple (gain, feat, bin, default_left, left_sum, right_sum)."""
    fs: FeatureSplits = best_split_per_feature(hist, leaf_sum, num_bins,
                                               is_cat, has_nan, params,
                                               monotone, bound, depth, cegb,
                                               contri, parent_out, rand_bins)
    gain = jnp.where(feature_mask, fs.gain, NEG_INF)
    f = jnp.argmax(gain)
    return (gain[f], f.astype(jnp.int32), fs.threshold_bin[f],
            fs.default_left[f], fs.left_sum[f], fs.right_sum[f],
            fs.cat_member[f])


class CommStrategy:
    """Serial (no-comm) strategy; parallel learners override the hooks.

    Hook contract inside the jitted grower:
      * ``reduce_sum(v)`` — reduce per-shard scalars/vectors over row shards
        (root grad/hess/count sums; DP/voting: ``psum``).
      * ``leaf_candidates(hist_local, leaf_sum, feature_mask, params)`` —
        best split for one leaf from the (possibly shard-local) histogram;
        must return a candidate with a GLOBAL feature index, identical on
        every device.
      * ``get_column(X_local, global_feat)`` — fetch the winning feature's
        bin column for the partition update (FP: owner broadcast).
      * ``local_meta(...)`` — slice per-feature descriptors to this shard's
        histogram width.
    """

    def __init__(self, num_bins, is_cat, has_nan, monotone=None):
        self.num_bins_full = num_bins
        self.is_cat_full = is_cat
        self.has_nan_full = has_nan
        self.monotone_full = monotone

    def reduce_sum(self, v):
        return v

    def reduce_max(self, v):
        """Cross-shard max (quantization scales; DP: pmax)."""
        return v

    def shard_key(self, key):
        """Decorrelate the stochastic-rounding PRNG stream per row shard
        (DP: fold in the axis index)."""
        return key

    def reduce_hist(self, hist):
        """Reduce a freshly built histogram across row shards (DP: psum —
        the analog of data_parallel_tree_learner.cpp:155's ReduceScatter+
        Allgather; voting keeps local histograms and reduces only the
        voted features inside leaf_candidates)."""
        return hist

    def local_meta(self, feature_mask):
        return (self.num_bins_full, self.is_cat_full, self.has_nan_full,
                feature_mask)

    def leaf_candidates(self, hist, leaf_sum, feature_mask, params,
                        bound=None, depth=None, parent_out=None,
                        rand_bins=None):
        nb, ic, hn, fm = self.local_meta(feature_mask)
        return local_best_candidate(hist, leaf_sum, nb, ic, hn, fm, params,
                                    self.monotone_full, bound, depth,
                                    getattr(self, "cegb_full", None),
                                    getattr(self, "contri_full", None),
                                    parent_out, rand_bins)

    def pair_candidates(self, hist_l, hist_r, lsum, rsum, feature_mask,
                        params, bound_l, bound_r, depth, fm_l=None,
                        fm_r=None, po_l=None, po_r=None, rb_l=None,
                        rb_r=None):
        """Both children's candidates in ONE vmapped scan (halves the
        per-split fixed cost of the dozens of small ops in the bin scan).
        fm_l/fm_r are optional per-child feature masks (bynode sampling);
        po_l/po_r the children's own smoothed outputs (path_smooth).
        Parallel strategies override with two sequential calls — their
        collectives are not vmap-batched."""
        hists = jnp.stack([hist_l, hist_r])
        sums = jnp.stack([lsum, rsum])
        nb, ic, hn, fm = self.local_meta(feature_mask)
        fms = jnp.stack([fm if fm_l is None else fm_l,
                         fm if fm_r is None else fm_r])
        if bound_l is None:
            bounds = jnp.zeros((2, 2), jnp.float32)
        else:
            bounds = jnp.stack([bound_l, bound_r])
        pos = jnp.zeros((2,), jnp.float32) if po_l is None \
            else jnp.stack([po_l, po_r])
        cegb = getattr(self, "cegb_full", None)
        contri = getattr(self, "contri_full", None)

        if rb_l is not None:
            rbs = jnp.stack([rb_l, rb_r])

            def one(h, s, b, f_m, po, rb):
                return local_best_candidate(h, s, nb, ic, hn, f_m, params,
                                            self.monotone_full, b, depth,
                                            cegb, contri, po, rb)

            out = jax.vmap(one)(hists, sums, bounds, fms, pos, rbs)
        else:
            def one(h, s, b, f_m, po):
                return local_best_candidate(h, s, nb, ic, hn, f_m, params,
                                            self.monotone_full, b, depth,
                                            cegb, contri, po)

            out = jax.vmap(one)(hists, sums, bounds, fms, pos)
        cl = tuple(o[0] for o in out)
        cr = tuple(o[1] for o in out)
        return cl, cr

    def get_column(self, X, feat):
        return jnp.take(X, feat, axis=1).astype(jnp.int32)


def make_grow_fn(*, num_leaves: int, max_bins: int, max_depth: int,
                 split_params: SplitParams, hist_impl: str,
                 rows_per_chunk: int, use_hist_pool: bool,
                 strategy: Optional[CommStrategy] = None, jit: bool = True):
    """Build the single-tree grower for a fixed configuration.

    The returned function signature is
    ``grow(X, X_T, grad, hess, sample_mask, num_bins, is_cat, has_nan,
    feature_mask) -> GrownTree`` where X may be the full binned matrix
    (serial), a row shard (data/voting parallel) or a feature shard
    (feature parallel) depending on the strategy.  ``X_T`` is the
    feature-major ``(F, N)`` copy used by the Pallas histogram kernel
    (None for the other impls); N must be padded to the kernel's row block.
    """

    hist_kwargs = dict(num_bins=max_bins, impl=hist_impl,
                       rows_per_chunk=rows_per_chunk)
    L = num_leaves
    if split_params.extra_trees:
        from ..utils.log import log_warning
        log_warning("extra_trees is not applied on this grower (pool-less "
                    "fallback / parallel learners); growing full scans")
    pallas = hist_impl == "pallas"
    if pallas:
        from ..ops.histogram_pallas import (DEFAULT_ROW_BLOCK,
                                            build_histogram_pallas)

    def _build_hist(X, X_T, g, h, m):
        if pallas:
            return build_histogram_pallas(X_T, g, h, m, num_bins=max_bins)
        return build_histogram(X, g, h, m, **hist_kwargs)

    use_mc = split_params.use_monotone
    use_sm = split_params.path_smooth > 0.0

    def _child_out(s3, parent_out):
        """Child leaf value: smoothed toward the parent when path_smooth
        is active (feature_histogram.hpp USE_SMOOTHING)."""
        if use_sm:
            from ..ops.split import leaf_output_smoothed
            return leaf_output_smoothed(s3[0], s3[1], s3[2], parent_out,
                                        split_params)
        return leaf_output(s3[0], s3[1], split_params)

    def grow(X: jnp.ndarray, X_T, grad: jnp.ndarray, hess: jnp.ndarray,
             sample_mask: jnp.ndarray, num_bins: jnp.ndarray,
             is_cat: jnp.ndarray, has_nan: jnp.ndarray,
             monotone: jnp.ndarray, feature_mask: jnp.ndarray) -> GrownTree:
        strat = strategy if strategy is not None else CommStrategy(
            num_bins, is_cat, has_nan, monotone)
        if strategy is not None:
            strat.monotone_full = monotone
        n, f_local = X.shape

        root_hist = strat.reduce_hist(
            _build_hist(X, X_T, grad, hess, sample_mask))
        root_sum = strat.reduce_sum(jnp.stack([
            jnp.sum(grad * sample_mask),
            jnp.sum(hess * sample_mask),
            jnp.sum(sample_mask)]))

        root_bound = jnp.asarray([-BIG, BIG], jnp.float32)
        root_out = _child_out(root_sum, jnp.asarray(0.0, jnp.float32))
        cand = strat.leaf_candidates(root_hist, root_sum, feature_mask,
                                     split_params, root_bound,
                                     jnp.asarray(0, jnp.int32), root_out)

        # Per-split child-row compaction buckets: the smaller child's rows
        # are gathered into the smallest adequate fixed-size buffer (a
        # power-of-4 ladder), so histogram work scales with the child's
        # size.  The leaf membership itself stays a per-row row_leaf vector
        # (DataPartition analog, data_partition.hpp:170) updated with masked
        # wheres — sequential full-N passes with a tiny constant beat
        # index-permutation bookkeeping on TPU, where random gather/scatter
        # is the expensive primitive.
        rows_sharded = getattr(strat, "rows_sharded", False)
        hist_buckets = []
        _size = (n // 2 + 1) if not rows_sharded else n
        if pallas:  # bucket sizes must be row-block multiples for the kernel
            _rb = DEFAULT_ROW_BLOCK
            _size = -(-_size // _rb) * _rb
            _top = _size
            while _size >= _rb and len(hist_buckets) < 4:
                hist_buckets.append(_size)
                _size = -(-(_size // 4) // _rb) * _rb
                if hist_buckets[-1] == _size:
                    break
        else:
            _top = _size
            while _size >= 4096 and len(hist_buckets) < 4:
                hist_buckets.append(_size)
                _size //= 4
        if not hist_buckets:
            hist_buckets = [_top]

        state = {
            "row_leaf": jnp.zeros((n,), jnp.int32),
            "leaf_sum": jnp.zeros((L, 3), jnp.float32).at[0].set(root_sum),
            "leaf_depth": jnp.zeros((L,), jnp.int32),
            "leaf_parent": jnp.full((L,), -1, jnp.int32),
            "cand_gain": jnp.full((L,), NEG_INF, jnp.float32).at[0].set(cand[0]),
            "cand_feat": jnp.zeros((L,), jnp.int32).at[0].set(cand[1]),
            "cand_bin": jnp.zeros((L,), jnp.int32).at[0].set(cand[2]),
            "cand_dleft": jnp.zeros((L,), jnp.bool_).at[0].set(cand[3]),
            "cand_lsum": jnp.zeros((L, 3), jnp.float32).at[0].set(cand[4]),
            "cand_rsum": jnp.zeros((L, 3), jnp.float32).at[0].set(cand[5]),
            "cand_member": jnp.zeros((L, max_bins), jnp.bool_).at[0].set(
                cand[6]),
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
        }
        if use_hist_pool:
            state["hists"] = jnp.zeros((L, f_local, max_bins, 3),
                                       jnp.float32).at[0].set(root_hist)
        if use_mc:
            state["leaf_mn"] = jnp.full((L,), -BIG, jnp.float32)
            state["leaf_mx"] = jnp.full((L,), BIG, jnp.float32)

        nb_full = strat.num_bins_full
        ic_full = strat.is_cat_full
        hn_full = strat.has_nan_full

        def body(t, s):
            best_leaf = jnp.argmax(s["cand_gain"]).astype(jnp.int32)
            bgain = s["cand_gain"][best_leaf]
            do = jnp.logical_and(jnp.logical_not(s["done"]), bgain > 0)
            dof = do.astype(jnp.float32)

            feat = s["cand_feat"][best_leaf]          # GLOBAL feature index
            thr = s["cand_bin"][best_leaf]
            dleft = s["cand_dleft"][best_leaf]
            lsum = s["cand_lsum"][best_leaf]
            rsum = s["cand_rsum"][best_leaf]
            member = s["cand_member"][best_leaf]      # (B,) categorical set
            psum_ = s["leaf_sum"][best_leaf]
            new_id = (t + 1).astype(jnp.int32)

            # ---- partition update (DataPartition::Split analog) ----
            col = strat.get_column(X, feat)
            fcat = ic_full[feat]
            fnan = hn_full[feat]
            f_nan_bin = jnp.where(fnan, nb_full[feat] - 1, -1)
            in_leaf = s["row_leaf"] == best_leaf
            is_nanbin = col == f_nan_bin
            go_left = jnp.where(fcat, member[col],
                                jnp.where(is_nanbin, dleft, col <= thr))
            row_leaf = jnp.where(do & in_leaf & jnp.logical_not(go_left),
                                 new_id, s["row_leaf"])
            # smaller side chosen by GLOBAL counts so every shard agrees
            # (GetGlobalDataCountInLeaf parity, parallel_tree_learner.h:67)
            left_smaller = lsum[2] <= rsum[2]

            if use_hist_pool:
                # one histogram pass over the SMALLER child + subtraction
                # (serial_tree_learner.cpp:311-320).  The child's rows are
                # compacted via cumsum + vectorized binary search (gather
                # only — jnp.nonzero's scatter is ~6x slower on TPU) into
                # the smallest adequate bucket.  The f32 running count is
                # exact up to 2^24 rows per shard; larger shards would need
                # a f64 cumsum here.
                small_id = jnp.where(left_smaller, best_leaf, new_id)
                small_mask = (row_leaf == small_id).astype(jnp.float32) * \
                    sample_mask * dof
                cs = jnp.cumsum(small_mask)
                small_cnt = cs[-1]

                def hist_branch(size):
                    def fn(cs_in):
                        q = jnp.arange(1, size + 1, dtype=jnp.float32)
                        idx = jnp.searchsorted(cs_in, q, side="left")
                        idx = jnp.where(q <= small_cnt, idx, n)
                        bsub = jnp.take(X, idx, axis=0, mode="fill",
                                        fill_value=0)
                        gsub = jnp.take(grad, idx, mode="fill", fill_value=0.0)
                        hsub = jnp.take(hess, idx, mode="fill", fill_value=0.0)
                        msub = jnp.take(small_mask, idx, mode="fill",
                                        fill_value=0.0)
                        return _build_hist(bsub, bsub.T if pallas else None,
                                           gsub, hsub, msub)
                    return fn

                if len(hist_buckets) == 1:
                    hist_small = hist_branch(hist_buckets[0])(cs)
                else:
                    sel = sum((small_cnt <= b).astype(jnp.int32)
                              for b in hist_buckets[1:])
                    hist_small = jax.lax.switch(
                        sel, [hist_branch(b) for b in hist_buckets], cs)
                hist_small = strat.reduce_hist(hist_small)
                parent_hist = s["hists"][best_leaf]
                hist_big = parent_hist - hist_small
                hist_left = jnp.where(left_smaller, hist_small, hist_big)
                hist_right = jnp.where(left_smaller, hist_big, hist_small)
            else:
                # no histogram pool (huge feature count): masked full passes
                left_mask = (row_leaf == best_leaf).astype(jnp.float32) * \
                    sample_mask * dof
                right_mask = (row_leaf == new_id).astype(jnp.float32) * \
                    sample_mask * dof
                hist_left = strat.reduce_hist(_build_hist(
                    X, X_T, grad, hess, left_mask))
                hist_right = strat.reduce_hist(_build_hist(
                    X, X_T, grad, hess, right_mask))

            # ---- monotone bounds for the children (BasicLeafConstraints::
            # Update, monotone_constraints.hpp:487-501: split outputs are
            # clamped to the leaf's bounds; the mid-point partitions the
            # output range between the children) ----
            parent_lv = s["leaf_value"][best_leaf]
            out_l = _child_out(lsum, parent_lv)
            out_r = _child_out(rsum, parent_lv)
            if use_mc:
                p_mn = s["leaf_mn"][best_leaf]
                p_mx = s["leaf_mx"][best_leaf]
                out_l = jnp.clip(out_l, p_mn, p_mx)
                out_r = jnp.clip(out_r, p_mn, p_mx)
                m = jnp.where(fcat, 0, monotone[feat])
                mid = (out_l + out_r) / 2.0
                mn_l = jnp.where(m < 0, jnp.maximum(p_mn, mid), p_mn)
                mx_l = jnp.where(m > 0, jnp.minimum(p_mx, mid), p_mx)
                mn_r = jnp.where(m > 0, jnp.maximum(p_mn, mid), p_mn)
                mx_r = jnp.where(m < 0, jnp.minimum(p_mx, mid), p_mx)
                bound_l = jnp.stack([mn_l, mx_l])
                bound_r = jnp.stack([mn_r, mx_r])
            else:
                bound_l = bound_r = None

            # ---- children candidates ----
            child_depth = s["leaf_depth"][best_leaf] + 1
            depth_ok = jnp.logical_or(max_depth <= 0, child_depth < max_depth)
            cl, cr = strat.pair_candidates(hist_left, hist_right, lsum, rsum,
                                           feature_mask, split_params,
                                           bound_l, bound_r, child_depth,
                                           po_l=out_l, po_r=out_r)
            gl = jnp.where(depth_ok, cl[0], NEG_INF)
            gr = jnp.where(depth_ok, cr[0], NEG_INF)

            # ---- tree arrays for node t ----
            node = t
            # categorical NaN rows live in bin 0 (most frequent category);
            # record default_left so raw-feature inference routes NaN the
            # same way the binned training partition did
            dleft = jnp.where(fcat, member[0], dleft)
            dt_bits = (jnp.where(fcat, CAT_MASK, 0) |
                       jnp.where(dleft, DEFAULT_LEFT_MASK, 0) |
                       jnp.where(fnan & jnp.logical_not(fcat), MISSING_NAN, 0)
                       ).astype(jnp.int32)
            parent_node = s["leaf_parent"][best_leaf]
            enc_best = -(best_leaf + 1)    # ~best_leaf
            node_idx = jnp.arange(L - 1, dtype=jnp.int32)
            patch_l = (node_idx == parent_node) & (s["left_child"] == enc_best) & do
            patch_r = (node_idx == parent_node) & (s["right_child"] == enc_best) & do
            left_child = jnp.where(patch_l, node, s["left_child"])
            right_child = jnp.where(patch_r, node, s["right_child"])

            def upd(arr, idx, val):
                return arr.at[idx].set(jnp.where(do, val, arr[idx]))

            out = dict(s)
            out["row_leaf"] = row_leaf
            if use_hist_pool:
                hists = s["hists"]
                hists = hists.at[best_leaf].set(
                    jnp.where(do, hist_left, hists[best_leaf]))
                hists = hists.at[new_id].set(
                    jnp.where(do, hist_right, hists[new_id]))
                out["hists"] = hists
            out["leaf_sum"] = upd(upd(s["leaf_sum"], best_leaf, lsum),
                                  new_id, rsum)
            out["leaf_depth"] = upd(upd(s["leaf_depth"], best_leaf, child_depth),
                                    new_id, child_depth)
            out["leaf_parent"] = upd(upd(s["leaf_parent"], best_leaf, node),
                                     new_id, node)
            out["cand_gain"] = upd(upd(s["cand_gain"], best_leaf, gl), new_id, gr)
            out["cand_feat"] = upd(upd(s["cand_feat"], best_leaf, cl[1]), new_id, cr[1])
            out["cand_bin"] = upd(upd(s["cand_bin"], best_leaf, cl[2]), new_id, cr[2])
            out["cand_dleft"] = upd(upd(s["cand_dleft"], best_leaf, cl[3]),
                                    new_id, cr[3])
            out["cand_lsum"] = upd(upd(s["cand_lsum"], best_leaf, cl[4]), new_id, cr[4])
            out["cand_rsum"] = upd(upd(s["cand_rsum"], best_leaf, cl[5]), new_id, cr[5])
            out["cand_member"] = upd(upd(s["cand_member"], best_leaf, cl[6]),
                                     new_id, cr[6])
            out["split_feature"] = upd(s["split_feature"], node, feat)
            out["threshold_bin"] = upd(s["threshold_bin"], node, thr)
            out["nan_bin"] = upd(s["nan_bin"], node, f_nan_bin)
            out["cat_member"] = upd(s["cat_member"], node, member)
            out["decision_type"] = upd(s["decision_type"], node, dt_bits)
            out["left_child"] = upd(left_child, node, enc_best)
            out["right_child"] = upd(right_child, node, -(new_id + 1))
            out["split_gain"] = upd(s["split_gain"], node, bgain)
            out["internal_value"] = upd(s["internal_value"], node,
                                        leaf_output(psum_[0], psum_[1],
                                                    split_params))
            out["internal_weight"] = upd(s["internal_weight"], node, psum_[1])
            out["internal_count"] = upd(s["internal_count"], node, psum_[2])
            if use_mc:
                out["leaf_mn"] = upd(upd(s["leaf_mn"], best_leaf, mn_l),
                                     new_id, mn_r)
                out["leaf_mx"] = upd(upd(s["leaf_mx"], best_leaf, mx_l),
                                     new_id, mx_r)
            lv = upd(s["leaf_value"], best_leaf, out_l)
            out["leaf_value"] = upd(lv, new_id, out_r)
            lw = upd(s["leaf_weight"], best_leaf, lsum[1])
            out["leaf_weight"] = upd(lw, new_id, rsum[1])
            lc = upd(s["leaf_count"], best_leaf, lsum[2])
            out["leaf_count"] = upd(lc, new_id, rsum[2])
            out["num_leaves"] = s["num_leaves"] + do.astype(jnp.int32)
            out["done"] = jnp.logical_not(do)
            return out

        s = jax.lax.fori_loop(0, L - 1, body, state)
        return GrownTree(
            split_feature=s["split_feature"], threshold_bin=s["threshold_bin"],
            nan_bin=s["nan_bin"], cat_member=s["cat_member"],
            decision_type=s["decision_type"],
            left_child=s["left_child"], right_child=s["right_child"],
            split_gain=s["split_gain"], internal_value=s["internal_value"],
            internal_weight=s["internal_weight"],
            internal_count=s["internal_count"], leaf_value=s["leaf_value"],
            leaf_weight=s["leaf_weight"], leaf_count=s["leaf_count"],
            num_leaves=s["num_leaves"], row_leaf=s["row_leaf"],
            **untracked_passes())

    return jax.jit(grow) if jit else grow


def resolve_hist_impl(config: Config, parallel: bool = False,
                      wave: bool = False, max_bins: int = 0) -> str:
    """Pick the histogram implementation (the analog of the reference's
    col-wise/row-wise autotune, dataset.cpp:659-670, collapsed to a static
    choice: the Pallas MXU kernel on TPU, scatter-add elsewhere).

    The SEQUENTIAL ``parallel`` growers (masked grower under shard_map)
    use the XLA onehot formulation on TPU — their per-split compaction
    path has no feature-major layout.  The WAVE grower keeps the Pallas
    leaf-batched kernel in both serial and shard_map form (``wave=True``;
    it owns the (F, N) layout natively)."""
    from ..utils.backend import default_backend
    impl = config.tpu_histogram_impl
    if impl == "auto":
        if default_backend() == "tpu":
            impl = "onehot" if (parallel and not wave) else "pallas"
        else:
            impl = "segment"
    elif impl == "pallas" and parallel and not wave:
        impl = "onehot"
    if impl == "packed4" and max_bins > 16:
        from ..utils.log import log_warning
        log_warning(f"tpu_histogram_impl=packed4 requires max_bin<=16 "
                    f"(got {max_bins}); using the segment path")
        impl = "segment"
    if impl == "pallas" and max_bins > 256:
        from ..utils.log import log_warning
        log_warning(f"max_bin={max_bins} exceeds the Pallas kernels' uint8 "
                    "bin range (256); using the XLA onehot histogram path "
                    "(uint16 bins) — set max_bin<=255 for peak TPU "
                    "throughput")
        impl = "onehot"
    return impl


def split_params_from_config(config: Config,
                             num_bins: Optional[np.ndarray] = None,
                             is_cat: Optional[np.ndarray] = None
                             ) -> SplitParams:
    mc = config.monotone_constraints or []
    use_mc = any(int(v) != 0 for v in mc)
    # monotone_constraints_method is a GROWER-level choice: the wave
    # growers implement 'intermediate' (region-box contiguity propagation,
    # learner/wave.py); other growers warn and use 'basic' — the warnings
    # are emitted where the grower is picked.
    # the sorted-subset categorical search is traced in only when some
    # categorical feature exceeds the one-hot threshold
    use_cat_subset = bool(
        num_bins is not None and is_cat is not None and
        np.any(np.asarray(is_cat) &
               (np.asarray(num_bins) > int(config.max_cat_to_onehot))))
    use_cegb = bool(config.cegb_penalty_split > 0.0 or
                    config.cegb_penalty_feature_coupled or
                    config.cegb_penalty_feature_lazy)
    return SplitParams(
        lambda_l1=float(config.lambda_l1),
        lambda_l2=float(config.lambda_l2),
        min_data_in_leaf=int(config.min_data_in_leaf),
        min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
        min_gain_to_split=float(config.min_gain_to_split),
        max_delta_step=float(config.max_delta_step),
        cat_l2=float(config.cat_l2),
        cat_smooth=float(config.cat_smooth),
        path_smooth=float(config.path_smooth),
        use_monotone=use_mc,
        monotone_penalty=float(config.monotone_penalty),
        max_cat_to_onehot=int(config.max_cat_to_onehot),
        max_cat_threshold=int(config.max_cat_threshold),
        min_data_per_group=int(config.min_data_per_group),
        use_cat_subset=use_cat_subset,
        use_cegb=use_cegb,
        cegb_tradeoff=float(config.cegb_tradeoff),
        cegb_penalty_split=float(config.cegb_penalty_split),
        feature_fraction_bynode=float(config.feature_fraction_bynode),
        extra_trees=bool(config.extra_trees),
        any_cat=bool(is_cat is None or np.any(np.asarray(is_cat))))
    # NOTE: cat_idx (the static cat-column positions that bound the
    # sorted-subset search) is NOT set here — scans that operate on
    # per-shard feature BLOCKS (feature-parallel, voting, DP
    # psum_scatter) index a sliced feature space where global positions
    # would be wrong.  Full-feature-space learners attach it via
    # ``sp._replace(cat_idx=...)``.


def resolve_monotone_method(config: Config, use_mc: bool,
                            wave: bool) -> bool:
    """Pick the intermediate-constraint flag for a grower and warn about
    downgrades (reference monotone_constraints.hpp:514/:856 — 'advanced'
    falls back to 'intermediate' on the wave growers; non-wave growers
    fall back to 'basic')."""
    method = str(config.monotone_constraints_method)
    if not use_mc or method == "basic":
        return False
    from ..utils.log import log_warning
    if not wave:
        log_warning(f"monotone_constraints_method='{method}' requires the "
                    "wave grower; falling back to 'basic' (safe but more "
                    "conservative bounds)")
        return False
    if method == "advanced":
        log_warning("monotone_constraints_method='advanced' is not "
                    "implemented; using 'intermediate' (less constraining "
                    "than basic, more than advanced)")
    return True


def hist_pool_fits(config: Config, num_features: int, max_bins: int) -> bool:
    """Keep per-leaf histograms when they fit the budget (reference
    histogram_pool_size, default -1 = a 1 GiB cap here to stay inside HBM
    alongside the data)."""
    pool_bytes = config.num_leaves * num_features * max_bins * 3 * 4
    budget = (float(config.histogram_pool_size) * (1 << 20)
              if config.histogram_pool_size > 0 else (1 << 30))
    return pool_bytes <= budget


# jitted growers cached by their full static configuration so repeated
# train() calls (tests, cv folds, sklearn fits) reuse compiled code.
# Bounded LRU: every live compiled executable holds process memory
# mappings and XLA:CPU segfaults when a process exhausts vm.max_map_count,
# so the cache drops the least-recently-used growers.  (This bounds the
# CACHE's contribution only — growers still referenced by live learners
# keep their executables mapped until those learners are released.)
_GROW_FN_CACHE: dict = {}
_GROW_FN_CACHE_MAX = 48


def _cache_put(key, fn):
    if len(_GROW_FN_CACHE) >= _GROW_FN_CACHE_MAX:
        _GROW_FN_CACHE.pop(next(iter(_GROW_FN_CACHE)))
    _GROW_FN_CACHE[key] = fn
    return fn


def _cache_hit(key):
    """LRU touch: move the hit entry to the back so cycling workloads
    (grid search over many configs) do not evict their hottest growers."""
    fn = _GROW_FN_CACHE.pop(key)
    _GROW_FN_CACHE[key] = fn
    return fn


def full_space_split_params(config: Config, num_bins, is_cat) -> SplitParams:
    """``split_params_from_config`` for a grower whose scans and row
    updates run in the FULL feature space (serial; the wave grower under
    any strategy): the static cat-column positions ride along (they
    bound the subset search's sorts and enable the embedding-style
    membership lookup)."""
    sp = split_params_from_config(config, num_bins, is_cat)
    cat = np.where(np.asarray(is_cat))[0]
    return sp._replace(cat_idx=tuple(int(j) for j in cat)) if len(cat) \
        else sp


def wave_grow_kwargs(config: Config, num_features: int, max_bins: int,
                     num_bins, is_cat, hist_impl: str, *, efb_dims=None,
                     efb_layout: tuple = (), forced_splits: tuple = (),
                     interaction_groups: tuple = (),
                     feature_contri: tuple = (), cegb_lazy: tuple = (),
                     strategy=None, sampled: bool = False,
                     acc_rows: int = 0) -> dict:
    """THE translation ``Config`` -> keyword arguments of
    ``learner/wave.py make_wave_grow_fn`` (all but ``jit``), for every
    learner that grows with it.  ``sampled``: the caller's row masks can
    hold zeros that ``config`` does not speak of (the masked folds of
    ``train_many``); a booster whose own sampling can
    (``Config.samples_rows``) needs no telling.  ``acc_rows``: the most
    rows a quantized histogram pass may add into one int32
    (ops/quantize.py ``hist_acc_rows``, from the data set's rows a shard
    and its fullest bin); 0, the narrow program, where no sum can wrap."""
    from ..ops.histogram_pallas import PACK4_MAX_BINS
    from ..ops.quantize import quant_levels
    any_cat = bool(np.any(np.asarray(is_cat)))
    sp = full_space_split_params(config, num_bins, is_cat)
    # kernel-v2 knobs: the DMA/blockspec pipeline choice and the 4-bit
    # packed bin layout (two codes per int8 lane when every feature fits
    # a nibble, reference dense_bin.hpp's 4-bit bins).  pack4 exists only
    # on the DMA pipeline: an explicit blockspec request (the
    # measured-dead-ends A/B knob) must actually run the v1 layout, so it
    # disables packing
    pipeline = (None if config.tpu_pallas_pipeline == "auto"
                else str(config.tpu_pallas_pipeline))
    pack4 = bool(config.tpu_hist_pack4 and hist_impl == "pallas" and
                 max_bins <= PACK4_MAX_BINS and not any_cat and
                 efb_dims is None and pipeline != "blockspec")
    if strategy is not None:
        # inherited, not chosen: the mesh wrappers never handed these two
        # on, so a mesh ignores tpu_hist_pack4 / tpu_pallas_pipeline
        # (ROADMAP D2: honour or raise)
        pack4, pipeline = False, None
    gq_max, hq_max = quant_levels(int(config.num_grad_quant_bins))
    return dict(
        num_leaves=int(config.num_leaves), num_features=num_features,
        max_bins=int(max_bins), max_depth=int(config.max_depth),
        split_params=sp, hist_impl=hist_impl, any_cat=any_cat,
        wave_size=int(config.tpu_wave_size), pack4=pack4, pipeline=pipeline,
        efb_dims=efb_dims, efb_layout=efb_layout,
        feature_contri=tuple(float(v) for v in feature_contri),
        strategy=strategy, quantized=bool(config.use_quantized_grad),
        gq_max=gq_max, hq_max=hq_max,
        renew_leaf=bool(config.quant_train_renew_leaf),
        stochastic=bool(config.stochastic_rounding),
        interaction_groups=tuple(tuple(g) for g in interaction_groups),
        cegb_lazy=tuple(float(v) for v in cegb_lazy),
        spec_ramp=bool(config.tpu_speculative_ramp),
        spec_tol=float(config.tpu_spec_tolerance),
        forced_splits=tuple(tuple(f) for f in forced_splits),
        mc_inter=resolve_monotone_method(config, sp.use_monotone, wave=True),
        exact_endgame=bool(config.tpu_exact_endgame),
        sampled=bool(sampled or config.samples_rows),
        hist_acc_rows=int(acc_rows) if config.use_quantized_grad else 0)


# grower arguments that leave the traced function alone in exact mode
_QUANT_ONLY = ("gq_max", "hq_max", "renew_leaf", "stochastic")


def feature_major_bins(X: jnp.ndarray, quantum: int,
                       pack4: bool = False) -> jnp.ndarray:
    """THE device layout of the bins the wave grower reads: rows padded
    up to a multiple of ``quantum``, then feature-major ``(F, N)``; under
    ``pack4`` nibble-packed to ``(F, N/2)`` (two 4-bit codes per int8
    lane).  Only this copy is consumed, so the padded row-major matrix is
    not kept alive next to it in HBM."""
    pad = (-X.shape[0]) % quantum
    Xp = jnp.pad(X, ((0, pad), (0, 0))) if pad else X
    xt = jnp.asarray(jnp.swapaxes(Xp, 0, 1))
    if pack4:
        from ..ops.histogram_pallas import pack_bins4
        xt = pack_bins4(xt.astype(jnp.uint8))
    return xt


class WaveTreeLearner:
    """The one host-side owner of the wave grower (learner/wave.py): the
    translation ``Config`` -> grower arguments (:func:`wave_grow_kwargs`),
    the device layout of the bins (:meth:`bind`) and the call convention
    of ``grow`` (:meth:`train`).

    ``mesh=None`` is one device: the grower is the jitted product cached
    in ``_GROW_FN_CACHE``.  With a ``mesh`` and its ``strategy``
    (``WaveDPStrategy`` / ``WaveVotingStrategy``) the rows are sharded
    over it and the grower runs under ``shard_map``
    (``shard_wave_grower``).  The serial, data-parallel and voting
    learners subclass it; each calls this ``__init__`` only if it grows
    by waves and keeps its other growers behind ``_train_other``."""

    wave = False            # the wave route was taken (__init__ ran)
    # what models/gbdt.py reads off any learner; set here and in
    # __init__ for the wave route, overridden where a subclass differs
    mesh = None             # the mesh the rows are sharded over, if any
    rows_sharded = False    # gbdt places per-row arrays on ``mesh``
    supports_extras = True  # train() takes cegb_penalty / node_key
    quantized = False       # train() wants a per-tree quant_key
    hist_acc_rows = 0       # rows a q8 pass adds into one int32 (0: all)
    grower_paths = None     # the wave grower's ``static_paths``

    def __init__(self, config: Config, num_features: int, max_bins: int,
                 num_bins: np.ndarray, is_cat: np.ndarray,
                 has_nan: np.ndarray, monotone: Optional[np.ndarray] = None,
                 *, hist_impl: str, efb=None, forced_splits: tuple = (),
                 interaction_groups: tuple = (), feature_contri: tuple = (),
                 cegb_lazy: tuple = (), mesh=None, strategy=None,
                 sampled: bool = False, acc_rows: int = 0):
        self._describe(config, num_features, max_bins, num_bins, is_cat,
                       has_nan, monotone, efb, mesh)
        self.wave = True
        self.pallas = hist_impl == "pallas"
        from .wave import make_wave_grow_fn
        self._grow_factory = make_wave_grow_fn
        kw = self._grow_kwargs = wave_grow_kwargs(
            config, num_features, self.max_bins, num_bins, is_cat, hist_impl,
            efb_dims=self._efb_dims,
            efb_layout=efb.layout() if efb is not None else (),
            forced_splits=forced_splits,
            interaction_groups=interaction_groups,
            feature_contri=feature_contri, cegb_lazy=cegb_lazy,
            strategy=strategy, sampled=sampled, acc_rows=acc_rows)
        self.hist_acc_rows = kw["hist_acc_rows"]
        self.split_params = sp = kw["split_params"]
        self.quantized = kw["quantized"]
        self.pack4 = kw["pack4"]
        # the optional operands of ``grow``, in their ONE order; which of
        # them ride along is static configuration
        self._use_lazy = bool(kw["cegb_lazy"])
        self._key_names = tuple(name for name, on in (
            ("quant_key", self.quantized),
            ("node_key", sp.feature_fraction_bynode < 1.0 or sp.extra_trees),
            ("lazy_used", self._use_lazy)) if on)
        self._lazy_used = None
        self._quant_calls = 0
        if mesh is None:
            # in exact mode the quant params don't affect the traced fn,
            # so they leave the key and sweeps over them don't recompile
            self._grow = self._cached_grow_fn(
                "wave", () if self.quantized else _QUANT_ONLY)
            self.grower_paths = dict(self._grow.static_paths)
            return
        from ..parallel.mesh import shard_wave_grower
        grow_w = self.build_grow_fn(jit=False)
        self.grower_paths = dict(grow_w.static_paths)
        names = self._key_names

        def grow(X_T, g, h, m, nb, ic, hn, mono, fm, cegb, *keys):
            return grow_w(X_T, g, h, m, nb, ic, hn, mono, cegb, (), fm,
                          **dict(zip(names, keys)))

        self._grow = shard_wave_grower(
            grow, mesh, self.axis, lazy=self._use_lazy,
            n_keys=len(names) - int(self._use_lazy))

    def _describe(self, config, num_features, max_bins, num_bins, is_cat,
                  has_nan, monotone, efb=None, mesh=None):
        """The dataset's static feature descriptors, as every grower of
        this learner is handed them, and the mesh it runs over."""
        self.config = config
        self.mesh = mesh
        self.ndev = 1 if mesh is None else mesh.devices.size
        self.axis = None if mesh is None else mesh.axis_names[0]
        self.efb = efb
        if efb is not None:
            self._efb_args = (jnp.asarray(efb.exp_map),
                              jnp.asarray(efb.f_bundle),
                              jnp.asarray(efb.f_offset),
                              jnp.asarray(efb.f_default),
                              jnp.asarray(efb.f_nbins),
                              jnp.asarray(efb.f_single))
            self._efb_dims = (int(efb.n_bundles), int(efb.bundle_bins))
        else:
            self._efb_args = ()
            self._efb_dims = None
        self.max_bins = int(max_bins)
        self.num_features = num_features
        self.num_bins = jnp.asarray(num_bins, jnp.int32)
        self.is_cat = jnp.asarray(is_cat, jnp.bool_)
        self.has_nan = jnp.asarray(has_nan, jnp.bool_)
        self.monotone = jnp.asarray(
            monotone if monotone is not None else np.zeros(num_features),
            jnp.int32)
        self._x_src = None
        self.setup_seconds = {}   # "layout": see bind()

    def build_grow_fn(self, split_params=None, jit: bool = True):
        """(Re)build this learner's grower from its recorded factory
        configuration.  ``split_params`` overrides the static SplitParams —
        the multi-model trainer (lightgbm_tpu/multitrain/) passes a
        variant carrying traced per-model scalars (ops/split.py
        TRACEABLE_PARAMS) and ``jit=False`` so it can vmap the raw grower
        over the model axis inside its own jitted step."""
        kw = dict(self._grow_kwargs)
        if split_params is not None:
            kw["split_params"] = split_params
        return self._grow_factory(jit=jit, **kw)

    def _cached_grow_fn(self, kind: str, unkeyed: tuple = ()):
        """This learner's jitted grower out of ``_GROW_FN_CACHE``, built
        on a miss.  The key is the factory's whole configuration: every
        grower argument is static."""
        key = (kind,) + tuple(sorted(
            (k, v) for k, v in self._grow_kwargs.items()
            if k not in unkeyed))
        if key not in _GROW_FN_CACHE:
            _cache_put(key, self.build_grow_fn())
        return _cache_hit(key)

    def bind(self, X_dev: jnp.ndarray) -> jnp.ndarray:
        """The ``(F, N)`` matrix ``grow`` is given for the row-major bins
        ``X_dev``, made once per matrix (:func:`feature_major_bins`, rows
        padded so that every shard meets the kernels' row block, placed
        as row shards on a mesh) and kept as ``_XpT``."""
        if self._x_src is not X_dev:  # strong ref: ids can be recycled
            # Host seconds of ENQUEUEING the pad + transpose (+ pack4);
            # the eager ops compile one module each, which takes no named
            # scope, so the device side has no `lgbm.` name
            with timed_span(self.setup_seconds, "layout", "train/layout"):
                if self.pallas:
                    from ..ops.histogram_pallas import DEFAULT_ROW_BLOCK
                    block = DEFAULT_ROW_BLOCK
                else:
                    # x8 so each shard's rows (and the packed lazy-CEGB
                    # bitmap's byte columns) stay 8-divisible; one device
                    # takes the rows as they come
                    block = 1 if self.mesh is None else 8
                xt = feature_major_bins(X_dev, self.ndev * block, self.pack4)
                if self.mesh is not None:
                    from ..parallel.mesh import shard_rows
                    xt = shard_rows(self.mesh, xt, self.axis, dim=1)
                self._XpT = xt
                self._lazy_used = None  # fresh data -> fresh used bitmap
            self._x_src = X_dev
        return self._XpT

    def train(self, X_dev: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
              sample_mask: jnp.ndarray,
              feature_mask: Optional[jnp.ndarray] = None,
              cegb_penalty: Optional[jnp.ndarray] = None,
              node_key: Optional[jnp.ndarray] = None,
              quant_key: Optional[jnp.ndarray] = None) -> GrownTree:
        if feature_mask is None:
            feature_mask = jnp.ones((self.num_features,), jnp.bool_)
        if cegb_penalty is None:
            cegb_penalty = jnp.zeros((self.num_features,), jnp.float32)
        if not self.wave:
            return self._train_other(X_dev, grad, hess, sample_mask,
                                     feature_mask, cegb_penalty, node_key)
        n = X_dev.shape[0]
        XpT = self.bind(X_dev)
        n_pad = XpT.shape[1] * (2 if self.pack4 else 1)
        rows = (grad, hess, sample_mask)
        if n_pad != n:
            rows = tuple(jnp.pad(v, (0, n_pad - n)) for v in rows)
        if self.mesh is not None:
            from ..parallel.mesh import shard_rows
            rows = tuple(shard_rows(self.mesh, v, self.axis) for v in rows)
        if self.quantized and quant_key is None:
            # per-call stream so direct callers (no gbdt driver threading
            # a per-tree key) still decorrelate the stochastic rounding
            # across trees
            self._quant_calls += 1
            quant_key = jax.random.PRNGKey(self._quant_calls)
        if node_key is None and "node_key" in self._key_names:
            node_key = jnp.zeros((2, 2), jnp.uint32)
        if self._use_lazy:
            # the used-feature bitmap persists across trees (the
            # reference's feature_used_in_data_ lives for the whole
            # training run)
            from .wave import LAZY_PACK, lazy_bitmap_init
            bitpack = n_pad % LAZY_PACK == 0  # pallas pads to 4096
            width = n_pad // LAZY_PACK if bitpack else n_pad
            if self._lazy_used is None or \
                    self._lazy_used.shape[1] != width:
                self._lazy_used = lazy_bitmap_init(
                    self.num_features, n_pad, bitpack)
        given = {"quant_key": quant_key, "node_key": node_key,
                 "lazy_used": self._lazy_used}
        keys = {name: given[name] for name in self._key_names}
        static = (self.num_bins, self.is_cat, self.has_nan, self.monotone)
        # each compiled program keeps the parameter order it was born
        # with (its executable is cached under it)
        if self.mesh is None:
            out = self._grow(XpT, *rows, *static, cegb_penalty,
                             self._efb_args, feature_mask, **keys)
        else:
            out = self._grow(XpT, *rows, *static, feature_mask,
                             cegb_penalty, *keys.values())
        if self._use_lazy:
            grown, self._lazy_used = out
        else:
            grown = out
        if n_pad != n:
            grown = grown._replace(row_leaf=grown.row_leaf[:n])
        return grown


class SerialTreeLearner(WaveTreeLearner):
    """Host-side wrapper: owns the jitted grower and the dataset's static
    feature descriptors (reference tree_learner.h:27 ``TreeLearner``).
    Chooses between the wave grower (its base class), the
    partition-ordered grower and the masked fallback."""

    def __init__(self, config: Config, num_features: int, max_bins: int,
                 num_bins: np.ndarray, is_cat: np.ndarray, has_nan: np.ndarray,
                 monotone: Optional[np.ndarray] = None,
                 forced_splits: tuple = (), efb=None,
                 interaction_groups: tuple = (),
                 feature_contri: tuple = (), cegb_lazy: tuple = (),
                 sampled: bool = False, acc_rows: int = 0):
        pool_f, pool_b = ((int(efb.n_bundles), int(efb.bundle_bins))
                          if efb is not None else (num_features, int(max_bins)))
        self.use_hist_pool = hist_pool_fits(config, pool_f, pool_b)
        if efb is not None and not self.use_hist_pool:
            raise ValueError("EFB requires the partitioned grower; raise "
                             "histogram_pool_size or disable enable_bundle")
        impl = resolve_hist_impl(config, max_bins=int(max_bins))
        if impl == "packed4" and efb is not None:
            # EFB histograms run in BUNDLE space whose bin count can
            # exceed the 4-bit range even when every feature fits it
            impl = "segment"
        if not self.use_hist_pool and impl == "pallas":
            # the pool-less fallback grower takes no transposed X and no row
            # padding — downgrade to the XLA onehot formulation (same MXU
            # math, without the VMEM layout contract)
            impl = "onehot"
        self.pallas = impl == "pallas"
        # The partition-ordered grower (learner/partitioned.py) is the
        # exact sequential serial path — no full-N work per split.  The
        # wave grower (learner/wave.py) trades row movement for MXU
        # leaf-batched histogram passes and wins on TPU.  The masked
        # grower below remains for the pool-less huge-feature fallback and
        # as the shared body of the parallel strategies.
        self.partitioned = self.use_hist_pool
        wave_ok = (self.use_hist_pool and int(config.num_leaves) > 2)
        mode = str(config.tree_grow_mode)
        if mode == "wave" and not wave_ok:
            from ..utils.log import log_warning
            log_warning("tree_grow_mode=wave is incompatible with "
                        "num_leaves<=2 / pool-less growth; "
                        "falling back to the partitioned grower")
            mode = "partition"
        elif mode == "auto":
            mode = "wave" if (wave_ok and impl == "pallas") else "partition"
        self.grow_mode = mode if self.use_hist_pool else "masked"
        self.pack4 = False
        if self.grow_mode == "wave":
            super().__init__(
                config, num_features, max_bins, num_bins, is_cat, has_nan,
                monotone, hist_impl=impl, efb=efb,
                forced_splits=forced_splits,
                interaction_groups=interaction_groups,
                feature_contri=feature_contri, cegb_lazy=cegb_lazy,
                sampled=sampled, acc_rows=acc_rows)
            return
        self._describe(config, num_features, max_bins, num_bins, is_cat,
                       has_nan, monotone, efb)
        self.split_params = full_space_split_params(config, num_bins, is_cat)
        resolve_monotone_method(config, self.split_params.use_monotone,
                                wave=False)
        if cegb_lazy:
            from ..utils.log import log_warning
            log_warning("cegb_penalty_feature_lazy is applied by the wave "
                        "grower only; this grower ignores it")
        if config.use_quantized_grad:
            from ..utils.log import log_warning
            log_warning("use_quantized_grad requires the wave grower "
                        "(tree_grow_mode=wave/auto on TPU); training "
                        "with exact gradients instead")
        if self.partitioned:
            from .partitioned import make_partitioned_grow_fn
            self._grow_factory = make_partitioned_grow_fn
            self._grow_kwargs = dict(
                num_leaves=int(config.num_leaves),
                num_features=num_features, max_bins=self.max_bins,
                max_depth=int(config.max_depth),
                split_params=self.split_params, hist_impl=impl,
                pipeline=(None if config.tpu_pallas_pipeline == "auto"
                          else str(config.tpu_pallas_pipeline)),
                forced_splits=tuple(tuple(f) for f in forced_splits),
                efb_dims=self._efb_dims,
                interaction_groups=tuple(
                    tuple(g) for g in interaction_groups),
                feature_contri=tuple(float(v) for v in feature_contri))
        else:
            self._grow_factory = make_grow_fn
            self._grow_kwargs = dict(
                num_leaves=int(config.num_leaves), max_bins=self.max_bins,
                max_depth=int(config.max_depth),
                split_params=self.split_params, hist_impl=impl,
                rows_per_chunk=int(config.tpu_rows_per_chunk),
                use_hist_pool=self.use_hist_pool)
        self._grow = self._cached_grow_fn(
            "part" if self.partitioned else "serial")

    def _train_other(self, X_dev, grad, hess, sample_mask, feature_mask,
                     cegb_penalty, node_key) -> GrownTree:
        if node_key is None:
            node_key = jnp.zeros((2, 2), jnp.uint32)
        if not self.partitioned:
            if self.split_params.use_cegb or \
                    self.split_params.feature_fraction_bynode < 1.0:
                from ..utils.log import log_warning
                log_warning("cegb / feature_fraction_bynode are not applied "
                            "on the pool-less fallback grower")
            return self._grow(X_dev, None, grad, hess, sample_mask,
                              self.num_bins, self.is_cat, self.has_nan,
                              self.monotone, feature_mask)
        n = X_dev.shape[0]
        if self.pallas:  # pad rows to the Pallas kernel's block
            from ..ops.histogram_pallas import pad_rows
            n_pad = pad_rows(n)
        else:
            n_pad = n
        pad = n_pad - n
        if self._x_src is not X_dev:  # strong ref: ids can be recycled
            # once per matrix: the row pad, timed like bind()'s layout
            with timed_span(self.setup_seconds, "layout", "train/layout"):
                self._Xp = jnp.pad(X_dev, ((0, pad), (0, 0))) if pad \
                    else X_dev
                self._x_src = X_dev
        if pad:
            grad = jnp.pad(grad, (0, pad))
            hess = jnp.pad(hess, (0, pad))
            sample_mask = jnp.pad(sample_mask, (0, pad))
        grown = self._grow(self._Xp, grad, hess, sample_mask,
                           self.num_bins, self.is_cat, self.has_nan,
                           self.monotone, cegb_penalty, node_key,
                           self._efb_args, feature_mask)
        if pad:
            grown = grown._replace(row_leaf=grown.row_leaf[:n])
        return grown
