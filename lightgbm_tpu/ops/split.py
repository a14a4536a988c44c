"""Vectorized split finding over histogram bins.

TPU-native replacement for FeatureHistogram's sequential threshold scan
(reference: src/treelearner/feature_histogram.hpp
``FindBestThresholdSequentially`` — a per-bin loop in two directions — and
``FindBestThresholdCategoricalInner``).  On TPU the scan becomes
bidirectional ``cumsum`` over the bin axis, all features at once; the
missing-direction double scan becomes two masked gain tensors; the argmax
replaces the reference's SplitInfo comparison ladder.

Gain / leaf-output closed forms follow feature_histogram.hpp:
  ThresholdL1(G, l1) = sign(G) * max(|G| - l1, 0)
  leaf_gain(G, H)    = ThresholdL1(G)^2 / (H + l2)
  output(G, H)       = -ThresholdL1(G) / (H + l2)   (clipped by max_delta_step)

Histograms arrive as (F, B, 3) float32 with channels (sum_grad, sum_hess,
count); our histograms keep every bin (no most-frequent-bin elision), so the
reference's ``Dataset::FixHistogram`` restore step is unnecessary.

Layout: internally the scan runs CHANNEL-SPLIT — three (F, B) planes with
the bin axis in the TPU lane dimension — because a trailing size-3 axis
would tile at 3/128 lane occupancy and make every cumsum/compare ~40x
slower than the arithmetic warrants.  The (F, B, 3) interface stays (it is
the histogram pool's storage layout); the transpose happens once at entry.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["SplitParams", "FeatureSplits", "best_split_per_feature",
           "best_split_two_bin", "leaf_output", "leaf_output_smoothed",
           "monotone_penalty_factor", "BIG"]

NEG_INF = -1e30


class SplitParams(NamedTuple):
    """Static split-finding hyperparameters (subset of Config)."""
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    path_smooth: float = 0.0
    use_monotone: bool = False     # any monotone_constraints nonzero
    monotone_penalty: float = 0.0
    # categorical split search (feature_histogram.hpp
    # FindBestThresholdCategoricalInner)
    max_cat_to_onehot: int = 4
    max_cat_threshold: int = 32
    min_data_per_group: int = 100
    use_cat_subset: bool = False   # any categorical feature needs the
                                   # sorted-subset search (num_bin > onehot)
    cat_idx: tuple = ()            # STATIC positions of categorical
                                   # features — the sorted-subset search
                                   # (two sorts per candidate) runs on this
                                   # slice only, not all F features
    # cost-effective gradient boosting (cost_effective_gradient_boosting
    # .hpp DeltaGain — upstream spells the method ``DetlaGain``):
    # gain -= tradeoff*(penalty_split*leaf_count +
    # coupled feature penalty when the feature is not yet used)
    use_cegb: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    feature_fraction_bynode: float = 1.0  # ColSampler by-node sampling
    extra_trees: bool = False  # one random threshold per feature per node
    any_cat: bool = True       # trace the categorical split search at all

BIG = 1e30  # "unbounded" leaf-output constraint sentinel

# SplitParams fields that MAY arrive as traced jax scalars instead of
# Python numbers: the multi-model trainer (lightgbm_tpu/multitrain/)
# sweeps them along a vmapped model axis, so one compiled program serves
# every hyperparameter variant.  They only ever flow through jnp
# arithmetic/comparisons below — never Python control flow — which keeps
# the traced and the constant-folded programs value-identical.
TRACEABLE_PARAMS = ("lambda_l1", "lambda_l2", "min_sum_hessian_in_leaf",
                    "min_data_in_leaf", "min_gain_to_split")


def params_are_static(params: "SplitParams") -> bool:
    """True when every traceable field is a plain Python number (the
    jit-with-static-params fast path); False when any is a jax value."""
    return not any(isinstance(getattr(params, k), (jax.Array, jax.core.Tracer))
                   for k in TRACEABLE_PARAMS)


class FeatureSplits(NamedTuple):
    """Per-feature best split (the vectorized SplitInfo,
    reference src/treelearner/split_info.hpp)."""
    gain: jnp.ndarray          # (F,) relative gain, NEG_INF when invalid
    threshold_bin: jnp.ndarray  # (F,) int32 bin threshold (or category bin)
    default_left: jnp.ndarray  # (F,) bool — direction for missing values
    left_sum: jnp.ndarray      # (F, 3)
    right_sum: jnp.ndarray     # (F, 3)
    cat_member: jnp.ndarray    # (F, B) bool — categorical LEFT-side bins


def _threshold_l1(g: jnp.ndarray, l1: float) -> jnp.ndarray:
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def _leaf_gain(g: jnp.ndarray, h: jnp.ndarray, l1: float, l2: float) -> jnp.ndarray:
    t = _threshold_l1(g, l1)
    return jnp.where(h + l2 > 0, t * t / (h + l2), 0.0)


def leaf_output(g: jnp.ndarray, h: jnp.ndarray, params: SplitParams) -> jnp.ndarray:
    """Closed-form leaf value (feature_histogram.hpp
    ``CalculateSplittedLeafOutput``)."""
    t = _threshold_l1(g, params.lambda_l1)
    out = jnp.where(h + params.lambda_l2 > 0, -t / (h + params.lambda_l2), 0.0)
    if params.max_delta_step > 0.0:
        out = jnp.clip(out, -params.max_delta_step, params.max_delta_step)
    return out


def leaf_output_smoothed(g, h, cnt, parent_out, params: SplitParams):
    """Leaf value with path smoothing (feature_histogram.hpp
    ``CalculateSplittedLeafOutput`` USE_SMOOTHING branch): the raw output
    shrinks toward the parent leaf's output by smooth/(n + smooth)."""
    t = _threshold_l1(g, params.lambda_l1)
    out = jnp.where(h + params.lambda_l2 > 0, -t / (h + params.lambda_l2), 0.0)
    # the reference clips the RAW output to +-max_delta_step first and
    # blends with the parent after (CalculateSplittedLeafOutput applies the
    # clip before the USE_SMOOTHING mix) — order matters when both are set
    if params.max_delta_step > 0.0:
        out = jnp.clip(out, -params.max_delta_step, params.max_delta_step)
    if params.path_smooth > 0.0:
        f = cnt / (cnt + params.path_smooth)
        out = out * f + parent_out * (1.0 - f)
    return out


def _gain_given_output(g, h, out, l1: float, l2: float):
    """Objective improvement of a leaf FORCED to value ``out`` (reference
    feature_histogram.hpp ``GetLeafGainGivenOutput``) — equals the standard
    closed-form gain when ``out`` is the unconstrained optimum."""
    t = _threshold_l1(g, l1)
    return -(2.0 * t * out + (h + l2) * out * out)


def monotone_penalty_factor(depth, penalty: float):
    """Gain multiplier for splits on monotone features
    (reference monotone_constraints.hpp:355
    ``ComputeMonotoneSplitGainPenalty``)."""
    eps = 1e-15
    d = depth.astype(jnp.float32)
    return jnp.where(penalty >= d + 1.0, eps,
                     jnp.where(penalty <= 1.0,
                               1.0 - penalty / jnp.exp2(d) + eps,
                               1.0 - jnp.exp2(penalty - 1.0 - d) + eps))


def _shift_left(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """``a`` moved ``k`` places toward index 0 along its last axis, zeros
    entering from the far end (``k`` static)."""
    n = a.shape[-1]
    if k >= n:
        return jnp.zeros_like(a)
    return jnp.concatenate(
        [a[..., k:], jnp.zeros(a.shape[:-1] + (k,), a.dtype)], axis=-1)


def _ratio_sorted_prefixes(ratio: jnp.ndarray, used: jnp.ndarray, planes):
    """The data movement of the sorted-subset search, by the sort network
    and static shifts (no gather, no scatter: both move one element at a
    time on a TPU).

    ``ratio`` (nc, B) is the sort key, ``used`` (nc,) how many of a row's
    keys are candidates (they sort first), ``planes`` the (nc, B) channel
    planes.  Returns ``rank`` (nc, B) int32, each bin's place in the stable
    ascending order of its row, and per plane ``(cumf, cumb)``: the prefix
    sums over the ``i + 1`` smallest and over the ``i + 1`` largest used
    ratios, the latter as ``total_used - cumf[used - 2 - i]``."""
    nc, b = ratio.shape
    iota = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[None, :], (nc, b))
    # one stable sort keyed on the ratio alone carries the bin ids and the
    # planes into ratio order; sorting the ids back carries the places
    _, order, *in_order = jax.lax.sort((ratio, iota, *planes), dimension=1,
                                       is_stable=True, num_keys=1)
    _, rank = jax.lax.sort((order, iota), dimension=1, num_keys=1)
    pos_used = iota < used[:, None]
    # cumf[used - 2 - i] is flip(cumf)[i + shift], shift = b + 1 - used in
    # [1, b + 1]: one select per bit of the shift between the plane and
    # its static shift (what runs off the far end is masked below anyway)
    shift = (b + 1 - used)[:, None]
    from_end = used[:, None] - 2 - iota >= 0
    out = []
    for sh in in_order:
        cumf = jnp.cumsum(jnp.where(pos_used, sh, 0.0), axis=1)
        tb = jnp.flip(cumf, axis=1)
        for bit in range((b + 1).bit_length()):
            tb = jnp.where(((shift >> bit) & 1) == 1,
                           _shift_left(tb, 1 << bit), tb)
        out.append((cumf, cumf[:, -1:] - jnp.where(from_end, tb, 0.0)))
    return rank, out


def best_split_per_feature(hist: jnp.ndarray, parent_sum: jnp.ndarray,
                           num_bins: jnp.ndarray, is_cat: jnp.ndarray,
                           has_nan: jnp.ndarray,
                           params: SplitParams,
                           monotone: Optional[jnp.ndarray] = None,
                           bound: Optional[jnp.ndarray] = None,
                           depth: Optional[jnp.ndarray] = None,
                           cegb_penalty: Optional[jnp.ndarray] = None,
                           gain_scale: Optional[jnp.ndarray] = None,
                           parent_out: Optional[jnp.ndarray] = None,
                           rand_bins: Optional[jnp.ndarray] = None
                           ) -> FeatureSplits:
    """Dispatch wrapper: static params take the jitted fast path (params
    hashable -> jit static arg); traced params (TRACEABLE_PARAMS carrying
    jax scalars, see multitrain) inline into the caller's trace."""
    if params_are_static(params):
        return _best_split_jit(hist, parent_sum, num_bins, is_cat, has_nan,
                               params, monotone, bound, depth, cegb_penalty,
                               gain_scale, parent_out, rand_bins)
    return _best_split_impl(hist, parent_sum, num_bins, is_cat, has_nan,
                            params, monotone, bound, depth, cegb_penalty,
                            gain_scale, parent_out, rand_bins)


def _best_split_impl(hist: jnp.ndarray, parent_sum: jnp.ndarray,
                     num_bins: jnp.ndarray, is_cat: jnp.ndarray,
                     has_nan: jnp.ndarray,
                     params: SplitParams,
                     monotone: Optional[jnp.ndarray] = None,
                     bound: Optional[jnp.ndarray] = None,
                     depth: Optional[jnp.ndarray] = None,
                     cegb_penalty: Optional[jnp.ndarray] = None,
                     gain_scale: Optional[jnp.ndarray] = None,
                     parent_out: Optional[jnp.ndarray] = None,
                     rand_bins: Optional[jnp.ndarray] = None
                     ) -> FeatureSplits:
    """Best split per feature from one leaf's histograms.

    Args:
      hist: (F, B, 3) float32 (grad, hess, count) histogram of the leaf.
      parent_sum: (3,) leaf totals (grad, hess, count).
      num_bins: (F,) int32 — actual bin count per feature (<= B), including
        the trailing NaN bin when has_nan.
      is_cat: (F,) bool — categorical features use one-vs-rest splits.
      has_nan: (F,) bool — feature's last bin holds NaN values.
      params: static hyperparameters.
      monotone/bound/depth: only read when ``params.use_monotone`` —
        per-feature ±1 constraint directions (F,), the leaf's (min, max)
        output bounds (2,), and the leaf's depth (for monotone_penalty).
    Returns:
      FeatureSplits with per-feature best candidates.

    Feature sub-range scans: F here may be any contiguous SLICE of the
    dataset's feature space — every per-feature operand (hist, num_bins,
    is_cat, has_nan, monotone, cegb_penalty, gain_scale, rand_bins) is
    indexed positionally, so shard-sliced scans (feature-parallel,
    voting, the DP reduce-scatter wave path) pass their block and remap
    the returned LOCAL indices to global feature space themselves.  The
    one exception is ``params.cat_idx``: those STATIC categorical
    positions index full feature space, so slice-scanned callers must
    leave it empty (the sorted-subset search then falls back to scanning
    all F slice columns) or avoid the sliced path for categorical shapes.
    """
    f, b, _ = hist.shape
    l1, l2 = params.lambda_l1, params.lambda_l2
    min_h = params.min_sum_hessian_in_leaf
    mdl = params.min_data_in_leaf
    min_cnt = (mdl.astype(jnp.float32)
               if isinstance(mdl, (jax.Array, jax.core.Tracer))
               else float(mdl))
    use_mc = params.use_monotone
    use_sm = params.path_smooth > 0.0
    use_out = use_mc or use_sm   # gains via explicit (possibly
    #                              constrained/smoothed) outputs
    if use_mc:
        mn, mx = bound[0], bound[1]
        mono = jnp.where(is_cat, 0, monotone)[:, None]           # (F, 1)

    if use_sm:
        # the leaf's own (smoothed) output is the smoothing target of its
        # children and defines the gain shift (GetLeafGain USE_SMOOTHING)
        parent_gain = _gain_given_output(parent_sum[0], parent_sum[1],
                                         parent_out, l1, l2)
    else:
        parent_gain = _leaf_gain(parent_sum[0], parent_sum[1], l1, l2)
    min_gain_shift = parent_gain + params.min_gain_to_split

    bins_r = jnp.arange(b, dtype=jnp.int32)[None, :]            # (1, B)
    nan_bin = (num_bins - 1)[:, None]                            # (F, 1)
    # per-(f,b) validity of a threshold: real-value bins only, and at least
    # one bin must remain on the right
    real_bin = jnp.where(has_nan[:, None], bins_r < nan_bin, bins_r < num_bins[:, None])
    if params.any_cat:
        # bin 0 of a categorical feature holds what is no binned category
        # (binning.py: missing, folded away, never seen); it is never a
        # candidate for a left set (feature_histogram.hpp bin_start = 1)
        real_bin = real_bin & jnp.logical_not(is_cat[:, None] & (bins_r == 0))
    thr_valid = jnp.where(has_nan[:, None],
                          bins_r < nan_bin,             # b in [0, nan_bin-1]
                          bins_r < num_bins[:, None] - 1)
    use_et = params.extra_trees and rand_bins is not None
    if use_et:
        # ExtraTrees (feature_histogram.hpp USE_RAND): evaluate ONE random
        # threshold per feature per node instead of the full bin scan
        thr_valid = thr_valid & (bins_r == rand_bins[:, None])

    # channel-split planes (F, B) — bins ride the lane dimension
    hg, hh, hc = hist[..., 0], hist[..., 1], hist[..., 2]
    # zero out bins beyond each feature's true range so cumsums are clean
    hg_m = jnp.where(real_bin, hg, 0.0)
    hh_m = jnp.where(real_bin, hh, 0.0)
    hc_m = jnp.where(real_bin, hc, 0.0)

    def at_bin(a, idx):
        """(F,) gather of one bin per feature from an (F, B) plane."""
        return jnp.take_along_axis(a, idx[:, None], 1)[:, 0]

    hn_f = has_nan[:, None]                                      # (F, 1)
    nan_g = jnp.where(hn_f, jnp.take_along_axis(hg, nan_bin, 1), 0.0)
    nan_h = jnp.where(hn_f, jnp.take_along_axis(hh, nan_bin, 1), 0.0)
    nan_c = jnp.where(hn_f, jnp.take_along_axis(hc, nan_bin, 1), 0.0)

    cum_g = jnp.cumsum(hg_m, axis=1)                             # (F, B)
    cum_h = jnp.cumsum(hh_m, axis=1)
    cum_c = jnp.cumsum(hc_m, axis=1)
    tot_g, tot_h, tot_c = parent_sum[0], parent_sum[1], parent_sum[2]

    def clamped_out(sg, sh, sc, l2_eff):
        """Split-child output with smoothing and/or constraint clamping
        (CalculateSplittedLeafOutput USE_SMOOTHING / USE_MC)."""
        t = _threshold_l1(sg, l1)
        h_ = sh + l2_eff
        out = jnp.where(h_ > 0, -t / h_, 0.0)
        # clip the raw output BEFORE the smoothing blend (the reference's
        # CalculateSplittedLeafOutput order); monotone clamping stays last
        if params.max_delta_step > 0.0:
            out = jnp.clip(out, -params.max_delta_step, params.max_delta_step)
        if use_sm:
            fac = sc / (sc + params.path_smooth)
            out = out * fac + parent_out * (1.0 - fac)
        return jnp.clip(out, mn, mx) if use_mc else out

    def dir_gain(lg, lh, lc):
        rg, rh, rc = tot_g - lg, tot_h - lh, tot_c - lc
        ok = ((lc >= min_cnt) & (rc >= min_cnt) &
              (lh >= min_h) & (rh >= min_h) & thr_valid)
        if use_out:
            # constrained/smoothed outputs (GetSplitGains USE_MC /
            # USE_SMOOTHING branches, feature_histogram.hpp): gain is
            # evaluated at the actually-deliverable output
            out_l = clamped_out(lg, lh, lc, l2)
            out_r = clamped_out(rg, rh, rc, l2)
            gl = _gain_given_output(lg, lh, out_l, l1, l2)
            gr = _gain_given_output(rg, rh, out_r, l1, l2)
            if use_mc:
                viol = (((mono > 0) & (out_l > out_r)) |
                        ((mono < 0) & (out_l < out_r)))
                ok = ok & jnp.logical_not(viol)
        else:
            gl = _leaf_gain(lg, lh, l1, l2)
            gr = _leaf_gain(rg, rh, l1, l2)
        g = gl + gr - min_gain_shift
        if use_mc and params.monotone_penalty > 0.0:
            pen = monotone_penalty_factor(depth, params.monotone_penalty)
            g = jnp.where(mono != 0, g * pen, g)
        return jnp.where(ok & (g > 0), g, NEG_INF)

    # numerical, missing->right (left = cum of real bins up to b)
    gain_r = dir_gain(cum_g, cum_h, cum_c)
    # numerical, missing->left (NaN bin joins the left side)
    gain_l = dir_gain(cum_g + nan_g, cum_h + nan_h, cum_c + nan_c)
    gain_l = jnp.where(hn_f, gain_l, NEG_INF)

    def _cat_search():
        """One-vs-rest and sorted-subset search over the categorical
        features: ``(gain (F,), left-set membership (F, B), left sums
        (F, 3))``."""
        # ---- categorical one-vs-rest: category bin b goes left, rest right
        # (feature_histogram.hpp FindBestThresholdCategoricalInner
        # one-hot branch, which keeps the plain lambda_l2: cat_l2 is added
        # in the sorted-subset branch only)
        cat_l2 = l2 + params.cat_l2
        crg, crh, crc = tot_g - hg_m, tot_h - hh_m, tot_c - hc_m
        if use_out:  # clamp/smooth outputs (no direction check for cats)
            c_out_l = clamped_out(hg_m, hh_m, hc_m, l2)
            c_out_r = clamped_out(crg, crh, crc, l2)
            cgl = _gain_given_output(hg_m, hh_m, c_out_l, l1, l2)
            cgr = _gain_given_output(crg, crh, c_out_r, l1, l2)
        else:
            cgl = _leaf_gain(hg_m, hh_m, l1, l2)
            cgr = _leaf_gain(crg, crh, l1, l2)
        cat_ok = ((hc_m >= min_cnt) & (crc >= min_cnt) &
                  (hh_m >= min_h) & (crh >= min_h) & real_bin)
        if use_et:  # one random category per node (USE_RAND one-hot branch)
            cat_ok = cat_ok & (bins_r == rand_bins[:, None])
        cat_gain = cgl + cgr - min_gain_shift
        cat_gain = jnp.where(cat_ok & (cat_gain > 0), cat_gain, NEG_INF)
        oh_bin = jnp.argmax(cat_gain, axis=1)
        oh_gain = at_bin(cat_gain, oh_bin)
        oh_member = jax.nn.one_hot(oh_bin, b, dtype=jnp.bool_)
        oh_left = jnp.stack([at_bin(hg_m, oh_bin), at_bin(hh_m, oh_bin),
                             at_bin(hc_m, oh_bin)], axis=-1)

        # ---- categorical sorted-subset search (feature_histogram.hpp
        # non-onehot branch): categories ordered by sum_grad/(sum_hess +
        # cat_smooth); prefix subsets scanned from BOTH ends, up to
        # max_cat_threshold categories; the LEFT child takes the subset.
        # It runs ONLY on the static cat columns (params.cat_idx) and its
        # results go back into F-space at the end: numeric features do not
        # ride through the two sorts.  No (nc, B) plane is moved by index
        # (_ratio_sorted_prefixes, at_pos): a gather of one costs a TPU
        # some 10 ns an element, 6-9 ms at 84 children x 26 columns x 256
        # bins against 0.3 ms for the five-operand sort (PERF.md, PR 37).
        if params.use_cat_subset:
            ci = jnp.asarray(params.cat_idx, jnp.int32) \
                if params.cat_idx else jnp.arange(f, dtype=jnp.int32)
            nc = len(params.cat_idx) or f
            hgc, hhc, hcc = hg_m[ci], hh_m[ci], hc_m[ci]
            real_bin_c = real_bin[ci]
            rand_bins_c = rand_bins[ci] if use_et else None
            mdpg = float(params.min_data_per_group)
            # candidate categories: count >= cat_smooth (the reference
            # reuses cat_smooth as the per-category min count filter)
            cat_valid = real_bin_c & (hcc >= params.cat_smooth)
            ratio = jnp.where(cat_valid,
                              hgc / (hhc + params.cat_smooth), BIG)
            used = jnp.sum(cat_valid, axis=1).astype(jnp.int32)  # (nc,)
            pos = jnp.arange(b, dtype=jnp.int32)[None, :]        # (1, B)
            rank, ((cumf_g, cumb_g), (cumf_h, cumb_h),
                   (cumf_c, cumb_c)) = _ratio_sorted_prefixes(
                       ratio, used, (hgc, hhc, hcc))

            max_pos = jnp.minimum(jnp.minimum(params.max_cat_threshold,
                                              (used[:, None] + 1) // 2),
                                  used[:, None])                 # (F, 1)
            pos_ok = pos < max_pos
            if use_et:  # one random subset size per node (USE_RAND)
                pos_ok = pos_ok & (pos == rand_bins_c[:, None] %
                                   jnp.maximum(max_pos, 1))

            def subset_gain(lg, lh, lc):
                rg, rh, rc = tot_g - lg, tot_h - lh, tot_c - lc
                # group spacing: the reference only evaluates a position
                # once >= min_data_per_group rows accumulated since the
                # last evaluated one; approximated here as crossing a
                # multiple of min_data_per_group in the prefix count
                gcross = jnp.floor(lc / mdpg)
                gprev = jnp.concatenate([jnp.full((nc, 1), -1.0),
                                         gcross[:, :-1]], axis=1)
                ok = (pos_ok & (lc >= min_cnt) & (lh >= min_h) &
                      (rc >= jnp.maximum(min_cnt, mdpg)) &
                      (rh >= min_h) & (gcross > gprev))
                if use_out:
                    o_l = clamped_out(lg, lh, lc, cat_l2)
                    o_r = clamped_out(rg, rh, rc, cat_l2)
                    gl_ = _gain_given_output(lg, lh, o_l, l1, cat_l2)
                    gr_ = _gain_given_output(rg, rh, o_r, l1, cat_l2)
                else:
                    gl_ = _leaf_gain(lg, lh, l1, cat_l2)
                    gr_ = _leaf_gain(rg, rh, l1, cat_l2)
                g = gl_ + gr_ - min_gain_shift
                return jnp.where(ok & (g > 0), g, NEG_INF)

            gain_f = subset_gain(cumf_g, cumf_h, cumf_c)
            gain_bk = subset_gain(cumb_g, cumb_h, cumb_c)

            def at_pos(a, idx):
                """One position a row of an (nc, B) plane, as a masked sum
                (exact: every other term is +0.0) and not as a gather."""
                return jnp.sum(jnp.where(pos == idx[:, None], a, 0.0), axis=1)

            f_pos = jnp.argmax(gain_f, axis=1)
            f_best = jnp.max(gain_f, axis=1)
            b_pos = jnp.argmax(gain_bk, axis=1)
            b_best = jnp.max(gain_bk, axis=1)
            use_bk = b_best > f_best
            sub_gain = jnp.where(use_bk, b_best, f_best)
            sub_pos = jnp.where(use_bk, b_pos, f_pos)
            sub_left = jnp.stack(
                [at_pos(jnp.where(use_bk[:, None], cb, cf), sub_pos)
                 for cf, cb in ((cumf_g, cumb_g), (cumf_h, cumb_h),
                                (cumf_c, cumb_c))], axis=-1)
            # membership: forward -> ranks [0, pos]; backward -> the top
            # (pos+1) ranks of the used range
            sub_member = jnp.where(
                use_bk[:, None],
                (rank >= used[:, None] - 1 - sub_pos[:, None]) &
                (rank < used[:, None]),
                rank <= sub_pos[:, None])

            # the nc-sliced results back into F-space (whole rows at
            # static places)
            sub_gain = jnp.full((f,), NEG_INF, hist.dtype).at[ci].set(
                sub_gain, mode="drop")
            sub_left = jnp.zeros((f, 3), hist.dtype).at[ci].set(
                sub_left, mode="drop")
            sub_member = jnp.zeros((f, b), jnp.bool_).at[ci].set(
                sub_member, mode="drop")

            use_subset = is_cat & (num_bins > params.max_cat_to_onehot)
            cat_best_gain = jnp.where(use_subset, sub_gain, oh_gain)
            cat_member = jnp.where(use_subset[:, None], sub_member, oh_member)
            cat_left_sum = jnp.where(use_subset[:, None], sub_left, oh_left)
        else:
            cat_best_gain = oh_gain
            cat_member = oh_member
            cat_left_sum = oh_left
        return cat_best_gain, cat_member, cat_left_sum

    if params.any_cat:
        # its own scope inside the caller's (the wave grower's
        # ``lgbm.wave.scan``): a trace tells the categorical search's
        # time from the numeric scan's; traced only where any_cat
        with jax.named_scope("lgbm.wave.cat_scan"):
            (cat_best_gain, cat_member,
             cat_left_sum) = _cat_search()
    else:
        # no categorical features in the dataset: the scan skips the
        # one-vs-rest/subset machinery entirely (is_cat is all-False, so
        # these dummies are never selected)
        cat_best_gain = jnp.full((f,), NEG_INF, hist.dtype)
        cat_member = jnp.zeros((f, b), jnp.bool_)
        cat_left_sum = jnp.zeros((f, 3), hist.dtype)

    # ---- numerical best over (bin, direction); categorical by mode ----
    best_r_bin = jnp.argmax(gain_r, axis=1)
    best_r_gain = at_bin(gain_r, best_r_bin)
    best_l_bin = jnp.argmax(gain_l, axis=1)
    best_l_gain = at_bin(gain_l, best_l_bin)

    use_left = best_l_gain > best_r_gain
    num_gain = jnp.where(use_left, best_l_gain, best_r_gain)
    num_thr = jnp.where(use_left, best_l_bin, best_r_bin).astype(jnp.int32)

    num_bin_pick = jnp.where(use_left, best_l_bin, best_r_bin)
    left_num = jnp.stack([at_bin(cum_g, num_bin_pick),
                          at_bin(cum_h, num_bin_pick),
                          at_bin(cum_c, num_bin_pick)], axis=-1)
    left_num = left_num + jnp.where(
        use_left[:, None],
        jnp.concatenate([nan_g, nan_h, nan_c], axis=1), 0.0)

    is_cat_b = is_cat[:, None]
    gain = jnp.where(is_cat, cat_best_gain, num_gain)
    if params.use_cegb:
        # constant per-feature penalty commutes with the per-bin argmax, so
        # it is applied to each feature's best (DeltaGain subtracted from
        # SplitInfo.gain in ComputeBestSplitForFeature)
        delta = (params.cegb_tradeoff * params.cegb_penalty_split *
                 parent_sum[2] +
                 (cegb_penalty if cegb_penalty is not None else 0.0))
        gain = jnp.where(gain > NEG_INF / 2, gain - delta, gain)
    if gain_scale is not None:
        # per-feature gain penalty (feature_contri; feature_histogram.hpp:94
        # ``output->gain *= meta_->penalty``)
        gain = jnp.where(gain > NEG_INF / 2, gain * gain_scale, gain)
    if params.any_cat:
        cat_member = cat_member & is_cat_b & (gain > NEG_INF / 2)[:, None]
        # cat threshold_bin kept as the first member bin (display/compat;
        # the partition decision uses the membership vector)
        cat_thr = jnp.argmax(cat_member, axis=1).astype(jnp.int32)
    else:
        # cat_member is the all-False constant here; running the argmax
        # anyway hands XLA a constant-foldable variadic (pred, iota)
        # reduce that costs >2s of compile time per vmapped scan on
        # multichip programs (seen as `%reduce.227` of an 8-device dry
        # run) — skip the reduce instead of folding it
        cat_thr = jnp.zeros((f,), jnp.int32)
    thr = jnp.where(is_cat, cat_thr, num_thr)
    left_sum = jnp.where(is_cat_b, cat_left_sum, left_num)
    right_sum = parent_sum[None, :] - left_sum

    return FeatureSplits(
        gain=gain,
        threshold_bin=thr,
        default_left=use_left & has_nan & jnp.logical_not(is_cat),
        left_sum=left_sum,
        right_sum=right_sum,
        cat_member=cat_member,
    )


_best_split_jit = functools.partial(jax.jit, static_argnames=("params",))(
    _best_split_impl)


def best_split_two_bin(left: jnp.ndarray, parent_sum: jnp.ndarray,
                       params: SplitParams,
                       monotone: Optional[jnp.ndarray] = None,
                       bound: Optional[jnp.ndarray] = None,
                       depth: Optional[jnp.ndarray] = None,
                       cegb_penalty: Optional[jnp.ndarray] = None,
                       gain_scale: Optional[jnp.ndarray] = None,
                       parent_out: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
    """Gain (F,) of the ONE split a numeric two-bin feature has, bin 0
    left | bin 1 right, with the FEATURES ON THE LANE AXIS: ``left``
    (3, F) holds bin 0's (sum_grad, sum_hess, count) of every feature.

    The arithmetic is :func:`best_split_per_feature`'s at threshold 0 of
    a (F, 2, 3) histogram, operation for operation (an (F, 2) plane would
    put 2 bins on 128 lanes); a trailing NaN bin changes nothing, since a
    split that leaves one side empty is never valid.  The wave grower's
    EFB scan calls it for the indicator columns of a bundle (efb.py
    ``make_scan_expand``); the other per-feature operands as there.
    The winner's left sums are ``left[:, f]``, its right sums the parent's
    less them, its threshold bin 0 and its default direction right."""
    l1, l2 = params.lambda_l1, params.lambda_l2
    min_h = params.min_sum_hessian_in_leaf
    mdl = params.min_data_in_leaf
    min_cnt = (mdl.astype(jnp.float32)
               if isinstance(mdl, (jax.Array, jax.core.Tracer))
               else float(mdl))
    use_mc = params.use_monotone
    use_sm = params.path_smooth > 0.0
    if use_sm:
        parent_gain = _gain_given_output(parent_sum[0], parent_sum[1],
                                         parent_out, l1, l2)
    else:
        parent_gain = _leaf_gain(parent_sum[0], parent_sum[1], l1, l2)
    min_gain_shift = parent_gain + params.min_gain_to_split
    lg, lh, lc = left[0], left[1], left[2]
    rg, rh, rc = parent_sum[0] - lg, parent_sum[1] - lh, parent_sum[2] - lc
    ok = (lc >= min_cnt) & (rc >= min_cnt) & (lh >= min_h) & (rh >= min_h)
    if use_mc or use_sm:
        def out_of(sg, sh, sc):
            t = _threshold_l1(sg, l1)
            h_ = sh + l2
            out = jnp.where(h_ > 0, -t / h_, 0.0)
            if params.max_delta_step > 0.0:
                out = jnp.clip(out, -params.max_delta_step,
                               params.max_delta_step)
            if use_sm:
                fac = sc / (sc + params.path_smooth)
                out = out * fac + parent_out * (1.0 - fac)
            return jnp.clip(out, bound[0], bound[1]) if use_mc else out
        out_l, out_r = out_of(lg, lh, lc), out_of(rg, rh, rc)
        gl = _gain_given_output(lg, lh, out_l, l1, l2)
        gr = _gain_given_output(rg, rh, out_r, l1, l2)
        if use_mc:
            ok = ok & jnp.logical_not(((monotone > 0) & (out_l > out_r)) |
                                      ((monotone < 0) & (out_l < out_r)))
    else:
        gl = _leaf_gain(lg, lh, l1, l2)
        gr = _leaf_gain(rg, rh, l1, l2)
    g = gl + gr - min_gain_shift
    if use_mc and params.monotone_penalty > 0.0:
        pen = monotone_penalty_factor(depth, params.monotone_penalty)
        g = jnp.where(monotone != 0, g * pen, g)
    gain = jnp.where(ok & (g > 0), g, NEG_INF)
    if params.use_cegb:
        delta = (params.cegb_tradeoff * params.cegb_penalty_split *
                 parent_sum[2] +
                 (cegb_penalty if cegb_penalty is not None else 0.0))
        gain = jnp.where(gain > NEG_INF / 2, gain - delta, gain)
    if gain_scale is not None:
        gain = jnp.where(gain > NEG_INF / 2, gain * gain_scale, gain)
    return gain
