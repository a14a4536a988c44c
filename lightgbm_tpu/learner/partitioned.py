"""Partition-ordered leaf-wise tree grower — the fast single-chip path.

TPU-native analog of the reference's DataPartition (data_partition.hpp:170):
where the reference keeps, per leaf, a contiguous span of row indices and
stable-partitions it on every split, this grower keeps the PACKED ROW DATA
itself leaf-contiguous.  Every per-split operation then works on chunked
``dynamic_slice``s of the split leaf's segment — there are NO full-N passes
per split (the v1 grower in serial.py pays several: mask rebuild, cumsum,
searchsorted compaction, full-N partition update), which is what dominated
its runtime at 255 leaves.

Packed layout ``P`` (N, W) uint8, leaf-segment ordered:

    [ bin codes (F) | grad f32 (4) | hess f32 (4) | orig row idx i32 (4)
      | bag byte (1) | zero pad to W ]

grad/hess are pre-multiplied by the bagging mask; the bag byte carries the
mask itself for the histogram count channel.  One packed row-scatter per
split moves each row of the split leaf to its child's side (rows move ~depth
times per tree, the same volume as the reference's index partition), and the
smaller child's histogram reads contiguous chunks — no gather at all —
feeding the Pallas MXU kernel (ops/histogram_pallas.py) or the portable
scatter-add path (CPU tests).

Segments are swept with ``lax.while_loop``s over exactly TWO static chunk
shapes (bulk + tail): static shapes keep XLA happy, dynamic trip counts keep
the work proportional to the segment, and — critically — the whole tree
compiles only two Pallas kernel shapes regardless of N.  (The previous
design used a power-of-two ladder of segment sizes: at 10.5M rows that
meant ~12 distinct kernel shapes per grower and multi-minute XLA compiles;
chunking killed the compile-time cliff and the per-split full-N work at
the same time.)

Leaf-wise semantics (best-first by gain, serial_tree_learner.cpp:158-209),
histogram subtraction trick (:311-320), and the split candidate logic are
identical to serial.py — the two growers are cross-checked by tests.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..models.tree import CAT_MASK, DEFAULT_LEFT_MASK, MISSING_NAN
from ..ops.histogram import build_histogram
from ..ops.split import (BIG, NEG_INF, _leaf_gain, leaf_output,
                         leaf_output_smoothed)
from .endgame import patch_child_pointers, write_split_records
from .serial import CommStrategy, GrownTree, untracked_passes

__all__ = ["make_partitioned_grow_fn", "PART_ROW_BLOCK"]

PART_ROW_BLOCK = 4096   # pad quantum; == Pallas kernel row-block contract
CHUNK_BULK = 1 << 20    # bulk sweep chunk (rows)
CHUNK_TAIL = 1 << 15    # tail sweep chunk (rows; 16K/64K measured worse)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def make_partitioned_grow_fn(*, num_leaves: int, num_features: int,
                             max_bins: int, max_depth: int, split_params,
                             hist_impl: str, interpret: bool = None,
                             pipeline: str = None,
                             jit: bool = True, forced_splits: tuple = (),
                             efb_dims=None, interaction_groups: tuple = (),
                             feature_contri: tuple = ()):
    """Build the partition-ordered single-tree grower.

    Returned signature:
    ``grow(X, grad, hess, bag_mask, num_bins, is_cat, has_nan, monotone,
    feature_mask) -> GrownTree`` with X (N, F) uint8 bin codes, N a multiple
    of PART_ROW_BLOCK (pad rows with bag_mask 0).
    """
    L = num_leaves
    F = num_features
    # EFB (lightgbm_tpu/efb.py): the packed matrix holds one column per
    # BUNDLE (G <= F) with Bb bundle bins; histograms live in bundle space
    # and are expanded to per-feature space right before each split scan
    use_efb = efb_dims is not None
    G, Bb = efb_dims if use_efb else (F, max_bins)
    W = _round_up(G + 13, 8)
    pallas = hist_impl == "pallas"
    if pallas:
        from ..ops.histogram_pallas import build_histogram_pallas

    sp = split_params
    use_mc = split_params.use_monotone
    use_sm = split_params.path_smooth > 0.0

    def _child_out(s3, parent_out):
        if use_sm:
            return leaf_output_smoothed(s3[0], s3[1], s3[2], parent_out, sp)
        return leaf_output(s3[0], s3[1], sp)
    bynode = split_params.feature_fraction_bynode < 1.0
    import math as _math
    kcnt = max(1, int(_math.ceil(F * split_params.feature_fraction_bynode))) \
        if bynode else F
    # interaction constraints (reference col_sampler.hpp GetByNode): at any
    # node, the allowed features are the union of constraint sets that
    # contain every feature already used on the branch path
    use_ic = len(interaction_groups) > 0
    if use_ic:
        import numpy as _np
        _g = _np.zeros((len(interaction_groups), F), bool)
        for gi, feats in enumerate(interaction_groups):
            for ff in feats:
                if 0 <= ff < F:
                    _g[gi, ff] = True
        ic_groups = jnp.asarray(_g)

        def allowed_features(path):
            compat = jnp.logical_not(
                jnp.any(path[None, :] & jnp.logical_not(ic_groups), axis=1))
            return jnp.any(ic_groups & compat[:, None], axis=0)

    # forced splits (serial_tree_learner.cpp:450 ForceSplits): BFS-ordered
    # (leaf, inner feature, threshold bin) triples applied before best-gain
    # growth; static per grower (they come from a config file)
    n_forced = min(len(forced_splits), L - 1)
    if n_forced:
        f_leaf_c = jnp.asarray([f[0] for f in forced_splits[:n_forced]],
                               jnp.int32)
        f_feat_c = jnp.asarray([f[1] for f in forced_splits[:n_forced]],
                               jnp.int32)
        f_bin_c = jnp.asarray([f[2] for f in forced_splits[:n_forced]],
                              jnp.int32)

    def _hist_from_seg(seg, valid):
        """(G, Bb, 3) bundle-space histogram of one packed chunk."""
        bins_rows = seg[:, :G]
        gm = jax.lax.bitcast_convert_type(seg[:, G:G + 4], jnp.float32)
        hm = jax.lax.bitcast_convert_type(seg[:, G + 4:G + 8], jnp.float32)
        bag = seg[:, G + 12].astype(jnp.float32)
        mask = bag * valid
        if pallas:
            return build_histogram_pallas(
                jnp.swapaxes(bins_rows, 0, 1), gm, hm, mask,
                num_bins=Bb, interpret=interpret, pipeline=pipeline)
        return build_histogram(bins_rows, gm, hm, mask, num_bins=Bb,
                               impl=hist_impl)

    def grow(X: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
             bag_mask: jnp.ndarray, num_bins: jnp.ndarray,
             is_cat: jnp.ndarray, has_nan: jnp.ndarray,
             monotone: jnp.ndarray, cegb_penalty: jnp.ndarray,
             node_key: jnp.ndarray, efb_arrays: tuple,
             feature_mask: jnp.ndarray) -> GrownTree:
        n = X.shape[0]
        strat = CommStrategy(num_bins, is_cat, has_nan, monotone)
        strat.cegb_full = cegb_penalty if split_params.use_cegb else None
        if feature_contri:
            strat.contri_full = jnp.asarray(feature_contri, jnp.float32)
        chunk_bulk = min(CHUNK_BULK, n)
        chunk_tail = min(CHUNK_TAIL, n)

        from ..efb import make_bundle_decode, make_expand_hist
        expand_hist = make_expand_hist(efb_arrays if use_efb else (),
                                       F, G, Bb)
        bundle_decode = make_bundle_decode(efb_arrays if use_efb else ())
        f_bundle = efb_arrays[1] if use_efb else None

        def feature_col(seg, feat, csize):
            """The FEATURE-space bin codes of one chunk for feature
            ``feat`` (reconstructed from its bundle column under EFB;
            efb.make_bundle_decode)."""
            g = f_bundle[feat] if use_efb else feat
            v = jax.lax.dynamic_slice(
                seg, (0, g), (csize, 1))[:, 0].astype(jnp.int32)
            return bundle_decode(v, feat)

        def node_mask(idx):
            """Exact-count per-node feature sample (ColSampler bynode,
            reference col_sampler.hpp).  node_key row 0 is the bynode
            stream (feature_fraction_seed)."""
            r = jax.random.uniform(jax.random.fold_in(node_key[0], idx),
                                   (F,))
            kth = jax.lax.top_k(r, kcnt)[0][-1]
            return r >= kth

        def node_rand(idx):
            """One random threshold bin per feature for this node
            (ExtraTrees, feature_histogram.hpp USE_RAND).  node_key row 1
            is the ExtraTrees stream (extra_seed) — independent of the
            bynode stream, like the reference's separate RNGs.  Numeric
            thresholds live in [0, nb-2]; categorical one-hot bins extend
            to nb-1 (the last category must stay reachable)."""
            u = jax.random.uniform(jax.random.fold_in(node_key[1], idx),
                                   (F,))
            hi = jnp.maximum(jnp.where(is_cat, num_bins - 1, num_bins - 2),
                             0)
            return jnp.minimum((u * (hi + 1).astype(jnp.float32)
                                ).astype(jnp.int32), hi)

        # ---- pack rows: bins | grad*bag | hess*bag | orig idx | bag ----
        gm = (grad * bag_mask).astype(jnp.float32)
        hm = (hess * bag_mask).astype(jnp.float32)
        P = jnp.concatenate([
            X.astype(jnp.uint8),
            jax.lax.bitcast_convert_type(gm, jnp.uint8),
            jax.lax.bitcast_convert_type(hm, jnp.uint8),
            jax.lax.bitcast_convert_type(
                jnp.arange(n, dtype=jnp.int32), jnp.uint8),
            (bag_mask > 0).astype(jnp.uint8)[:, None],
            jnp.zeros((n, W - G - 13), jnp.uint8),
        ], axis=1)

        def _sweep(start, cnt, fn, carry):
            """Run ``fn(chunk_start, chunk_size(static), carry)`` over the
            segment [start, start+cnt): bulk chunks first, then tail
            chunks.  fn must itself mask rows outside [start, start+cnt)."""
            nb = cnt // chunk_bulk

            def bulk(i, c):
                return fn(start + i * chunk_bulk, chunk_bulk, c)

            carry = jax.lax.fori_loop(0, nb, bulk, carry)
            t0 = start + nb * chunk_bulk
            nt = (cnt - nb * chunk_bulk + chunk_tail - 1) // chunk_tail

            def tail(i, c):
                return fn(t0 + i * chunk_tail, chunk_tail, c)

            return jax.lax.fori_loop(0, nt, tail, carry)

        def _chunk_rows(cstart, csize):
            """Load a (csize, W) slice whose row j is global row
            ``clamped + j`` (dynamic_slice clamps near the array end)."""
            clamped = jnp.minimum(cstart, n - csize)
            seg = jax.lax.dynamic_slice(P_ref[0], (clamped, 0), (csize, W))
            return seg, clamped

        # P is rebound per split inside the fori_loop; the sweep helpers
        # read it through this one-element list closure.  The two staging
        # buffers (sized n + one bulk chunk so full-chunk stores never
        # clamp) are scratch carried through the loop for reuse; their
        # stale contents are never read (the combine pass only reads
        # positions the current split wrote).
        P_ref = [P]
        # L stacks lefts ASCENDING from the segment start (tail slack of
        # one bulk chunk absorbs full-chunk store overhang); R stacks
        # rights DESCENDING from the fixed top T0 = n + chunk_bulk, so it
        # needs one bulk chunk of slack on BOTH sides: below T0-nr for
        # each store's garbage overhang, above n for nothing-but-sizing
        # symmetry of the store bounds (see partition_segment).
        stage_ref = [jnp.zeros((n + chunk_bulk, W), jnp.uint8),
                     jnp.zeros((n + 2 * chunk_bulk, W), jnp.uint8)]

        def hist_of_segment(start, cnt):
            def step(cstart, csize, acc):
                seg, clamped = _chunk_rows(cstart, csize)
                j = jnp.arange(csize, dtype=jnp.int32)
                gpos = clamped + j
                valid = ((gpos >= cstart) & (gpos < start + cnt)
                         ).astype(jnp.float32)
                return acc + _hist_from_seg(seg, valid)

            acc0 = jnp.zeros((G, Bb, 3), jnp.float32)
            return _sweep(start, cnt, step, acc0)

        def _decide_col(col, clamped, cstart, cend, csize, feat_args):
            feat, thr, dleft, fcat, fnanb, member = feat_args
            j = jnp.arange(csize, dtype=jnp.int32)
            gpos = clamped + j
            valid = (gpos >= cstart) & (gpos < cend)
            is_nanbin = col == fnanb
            go_left = jnp.where(fcat, member[col],
                                jnp.where(is_nanbin, dleft, col <= thr))
            return go_left & valid, valid

        def partition_segment(start, cnt, feat, thr, dleft, fcat, fnanb,
                              member):
            """Stable chunked partition of [start, start+cnt)
            (DataPartition::Split analog), built from BANDWIDTH-friendly
            primitives: XLA row scatter costs ~150ns/row on TPU, so instead
            each chunk is stable-sorted lefts-first (multi-operand
            ``lax.sort`` on a 1-bit key, ~37ns/row) and written with TWO
            full-chunk contiguous stores into left/right staging buffers at
            final positions (garbage tails are overwritten by the next
            chunk or masked at combine); a final contiguous sweep selects
            staging rows back into P by position.  Returns (P_new, n_left).
            """
            feat_args = (feat, thr, dleft, fcat, fnanb, member)
            cend = start + cnt

            # pass A: per-chunk stable sort + staged contiguous writes.
            # Lefts land in the L staging buffer at their FINAL positions,
            # stacked ASCENDING from ``start``; rights are stacked
            # DESCENDING from the fixed top T0 of the R buffer.  Both
            # directions share the same correctness argument: each store's
            # valid run abuts the previous watermark and its garbage lies
            # strictly beyond the NEW watermark, so the last writer of any
            # position inside the final valid range wrote valid rows there
            # — for ANY mix of chunk sizes.  (An earlier version staged
            # rights ascending at (dr - clt): each chunk's left-garbage
            # then landed BELOW the right watermark, silently clobbering
            # the previous chunks' staged rights whenever a segment
            # spanned multiple chunks.)  One shared buffer would be
            # unsafe: the left/right full-chunk stores collide.
            Wq = W // 4
            T0 = n + chunk_bulk   # top of the descending rights stack

            def stage_step(cstart, csize, carry):
                Lb, Rb, dl, dr = carry
                seg, clamped = _chunk_rows(cstart, csize)
                col = feature_col(seg, feat, csize)
                gl, valid = _decide_col(col, clamped, cstart, cend, csize,
                                        feat_args)
                # order [lefts | invalid | rights]: lefts at the chunk
                # BOTTOM feed the ascending L stack, rights at the chunk
                # TOP feed the descending R stack — garbage (including the
                # invalid middle) then always falls on the safe side of
                # both watermarks
                key = jnp.where(gl, 0, jnp.where(valid, 2, 1))
                cols = jax.lax.bitcast_convert_type(
                    seg.reshape(csize, Wq, 4), jnp.int32)
                ops = [key] + [cols[:, k] for k in range(Wq)]
                out = jax.lax.sort(ops, dimension=0, is_stable=True,
                                   num_keys=1)
                sorted_u8 = jax.lax.bitcast_convert_type(
                    jnp.stack(out[1:], axis=1), jnp.uint8).reshape(csize, W)
                clt = jnp.sum(gl.astype(jnp.int32))
                crt = jnp.sum(valid.astype(jnp.int32)) - clt
                # lefts: rows [0, clt) stored at the ascending watermark
                Lb = jax.lax.dynamic_update_slice(
                    Lb, sorted_u8, (start + dl, 0))
                # rights: the chunk's TOP crt rows land at [T0-dr-crt,
                # T0-dr) — the descending watermark; left/invalid garbage
                # falls strictly below it and is overwritten by later
                # chunks or ignored by the combine's nr bound.  Segment
                # order of rights becomes chunk-reversed, which is
                # irrelevant: row order within a leaf segment is free.
                Rb = jax.lax.dynamic_update_slice(
                    Rb, sorted_u8, (T0 - dr - csize, 0))
                return Lb, Rb, dl + clt, dr + crt

            Lb, Rb, nl, nr = _sweep(start, cnt, stage_step,
                                    (stage_ref[0], stage_ref[1],
                                     jnp.asarray(0, jnp.int32),
                                     jnp.asarray(0, jnp.int32)))
            stage_ref[0] = Lb
            stage_ref[1] = Rb

            # combine: contiguous sweep selecting Lb below start+nl, and
            # the rights block [T0-nr, T0) above
            def combine_step(cstart, csize, P_out):
                clamped = jnp.minimum(cstart, n - csize)
                lrow = jax.lax.dynamic_slice(Lb, (clamped, 0), (csize, W))
                rrow = jax.lax.dynamic_slice(
                    Rb, (jnp.maximum(clamped - (start + nl) + T0 - nr, 0),
                         0), (csize, W))
                cur = jax.lax.dynamic_slice(P_out, (clamped, 0), (csize, W))
                j = jnp.arange(csize, dtype=jnp.int32)
                gpos = clamped + j
                inseg = (gpos >= start) & (gpos < cend)
                use_l = gpos < start + nl
                rows = jnp.where(
                    inseg[:, None],
                    jnp.where(use_l[:, None], lrow, rrow), cur)
                return jax.lax.dynamic_update_slice(P_out, rows, (clamped, 0))

            P_out = _sweep(start, cnt, combine_step, P_ref[0])
            return P_out, nl, Lb, Rb

        root_hist = hist_of_segment(jnp.asarray(0, jnp.int32),
                                    jnp.asarray(n, jnp.int32))
        root_sum = jnp.stack([jnp.sum(gm), jnp.sum(hm), jnp.sum(bag_mask)])
        root_bound = jnp.asarray([-BIG, BIG], jnp.float32)
        fm_root = feature_mask & node_mask(2 * L) if bynode else feature_mask
        if use_ic:
            fm_root = fm_root & allowed_features(
                jnp.zeros((F,), jnp.bool_))
        root_out = _child_out(root_sum, jnp.asarray(0.0, jnp.float32))
        rb_root = node_rand(2 * L) if sp.extra_trees else None
        cand = strat.leaf_candidates(expand_hist(root_hist, root_sum),
                                     root_sum, fm_root, sp,
                                     root_bound, jnp.asarray(0, jnp.int32),
                                     root_out, rb_root)

        state = {
            "P": P,
            "stageL": stage_ref[0],
            "stageR": stage_ref[1],
            "leaf_start": jnp.full((L,), n, jnp.int32).at[0].set(0),
            "leaf_seg": jnp.zeros((L,), jnp.int32).at[0].set(n),
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
            "hists": jnp.zeros((L, G, Bb, 3), jnp.float32).at[0].set(
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
        }
        if use_ic:
            state["leaf_path"] = jnp.zeros((L, F), jnp.bool_)
        if use_mc:
            state["leaf_mn"] = jnp.full((L,), -BIG, jnp.float32)
            state["leaf_mx"] = jnp.full((L,), BIG, jnp.float32)

        nb_full, ic_full, hn_full = num_bins, is_cat, has_nan

        def body(t, s):
            P_ref[0] = s["P"]
            stage_ref[0] = s["stageL"]
            stage_ref[1] = s["stageR"]
            best_leaf = jnp.argmax(s["cand_gain"]).astype(jnp.int32)
            bgain = s["cand_gain"][best_leaf]
            do = jnp.logical_and(jnp.logical_not(s["done"]), bgain > 0)

            feat = s["cand_feat"][best_leaf]
            thr = s["cand_bin"][best_leaf]
            dleft = s["cand_dleft"][best_leaf]
            lsum = s["cand_lsum"][best_leaf]
            rsum = s["cand_rsum"][best_leaf]
            member = s["cand_member"][best_leaf]

            if n_forced:
                # ForceSplits override: fixed (leaf, feature, bin) applied
                # regardless of gain; child sums read from the leaf's
                # pooled histogram
                fi = jnp.minimum(t, n_forced - 1)
                is_forced = t < n_forced
                best_leaf = jnp.where(is_forced, f_leaf_c[fi], best_leaf)
                feat = jnp.where(is_forced, f_feat_c[fi], feat)
                thr = jnp.where(is_forced, f_bin_c[fi], thr)
                dleft = jnp.where(is_forced, False, dleft)
                member = jnp.where(is_forced, jnp.zeros_like(member), member)
                fh = expand_hist(s["hists"][best_leaf],
                                 s["leaf_sum"][best_leaf])[feat]   # (B, 3)
                csum = jnp.cumsum(fh, axis=0)
                lsum_f = csum[jnp.clip(thr, 0, max_bins - 1)]
                rsum_f = s["leaf_sum"][best_leaf] - lsum_f
                lsum = jnp.where(is_forced, lsum_f, lsum)
                rsum = jnp.where(is_forced, rsum_f, rsum)
                # record the forced split's REAL gain (scan-scale), not 0
                psum_f = s["leaf_sum"][best_leaf]
                gain_f = (_leaf_gain(lsum_f[0], lsum_f[1],
                                     split_params.lambda_l1,
                                     split_params.lambda_l2) +
                          _leaf_gain(rsum_f[0], rsum_f[1],
                                     split_params.lambda_l1,
                                     split_params.lambda_l2) -
                          _leaf_gain(psum_f[0], psum_f[1],
                                     split_params.lambda_l1,
                                     split_params.lambda_l2) -
                          split_params.min_gain_to_split)
                bgain = jnp.where(is_forced, gain_f, bgain)
                do = jnp.where(is_forced,
                               s["leaf_seg"][best_leaf] > 0, do)
            psum_ = s["leaf_sum"][best_leaf]
            new_id = (t + 1).astype(jnp.int32)

            start = s["leaf_start"][best_leaf]
            seg_cnt = jnp.where(do, s["leaf_seg"][best_leaf], 0)
            fcat = ic_full[feat]
            fnan = hn_full[feat]
            f_nan_bin = jnp.where(fnan, nb_full[feat] - 1, -1)

            P_new, nl, stage_l, stage_r = partition_segment(
                start, seg_cnt, feat, thr, dleft, fcat, f_nan_bin, member)
            nr = seg_cnt - nl
            P_ref[0] = P_new

            # ---- smaller-child histogram on its contiguous segment ----
            left_smaller = lsum[2] <= rsum[2]
            s_start = jnp.where(left_smaller, start, start + nl)
            s_cnt = jnp.where(do, jnp.where(left_smaller, nl, nr), 0)
            hist_small = hist_of_segment(s_start, s_cnt)
            parent_hist = s["hists"][best_leaf]
            hist_big = parent_hist - hist_small
            hist_left = jnp.where(left_smaller, hist_small, hist_big)
            hist_right = jnp.where(left_smaller, hist_big, hist_small)

            # ---- monotone bounds for the children (BasicLeafConstraints::
            # Update, monotone_constraints.hpp:487-501) ----
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

            # ---- children candidates (one vmapped scan for the pair) ----
            child_depth = s["leaf_depth"][best_leaf] + 1
            depth_ok = jnp.logical_or(max_depth <= 0, child_depth < max_depth)
            if bynode:
                fm_l = feature_mask & node_mask(2 * t)
                fm_r = feature_mask & node_mask(2 * t + 1)
            else:
                fm_l = fm_r = None
            if use_ic:
                child_path = s["leaf_path"][best_leaf] | \
                    (jnp.arange(F) == feat)
                allowed = allowed_features(child_path)
                fm_l = (feature_mask if fm_l is None else fm_l) & allowed
                fm_r = (feature_mask if fm_r is None else fm_r) & allowed
            rb_l = node_rand(2 * t) if sp.extra_trees else None
            rb_r = node_rand(2 * t + 1) if sp.extra_trees else None
            cl, cr = strat.pair_candidates(
                expand_hist(hist_left, lsum), expand_hist(hist_right, rsum),
                lsum, rsum, feature_mask, sp, bound_l, bound_r,
                child_depth, fm_l, fm_r, out_l, out_r, rb_l, rb_r)
            gl_ = jnp.where(depth_ok, cl[0], NEG_INF)
            gr_ = jnp.where(depth_ok, cr[0], NEG_INF)

            node = t
            dleft_rec = jnp.where(fcat, member[0], dleft)
            dt_bits = (jnp.where(fcat, CAT_MASK, 0) |
                       jnp.where(dleft_rec, DEFAULT_LEFT_MASK, 0) |
                       jnp.where(fnan & jnp.logical_not(fcat), MISSING_NAN, 0)
                       ).astype(jnp.int32)
            # sequential selector bookkeeping shared with the wave
            # grower's exact endgame (learner/endgame.py): the split
            # leaf's unique -(leaf+1) child-slot code is patched to the
            # committed node — no parent-index tracking needed
            left_child, right_child = patch_child_pointers(
                s["left_child"], s["right_child"], best_leaf, node,
                active=do)

            def upd(arr, idx, val):
                return arr.at[idx].set(jnp.where(do, val, arr[idx]))

            out = dict(s)
            out["P"] = P_new
            out["stageL"] = stage_l
            out["stageR"] = stage_r
            out["leaf_start"] = upd(upd(s["leaf_start"], best_leaf, start),
                                    new_id, start + nl)
            out["leaf_seg"] = upd(upd(s["leaf_seg"], best_leaf, nl),
                                  new_id, nr)
            hists = s["hists"]
            hists = hists.at[best_leaf].set(
                jnp.where(do, hist_left, hists[best_leaf]))
            hists = hists.at[new_id].set(
                jnp.where(do, hist_right, hists[new_id]))
            out["hists"] = hists
            out["leaf_sum"] = upd(upd(s["leaf_sum"], best_leaf, lsum),
                                  new_id, rsum)
            out["leaf_depth"] = upd(upd(s["leaf_depth"], best_leaf,
                                        child_depth), new_id, child_depth)
            out["cand_gain"] = upd(upd(s["cand_gain"], best_leaf, gl_),
                                   new_id, gr_)
            out["cand_feat"] = upd(upd(s["cand_feat"], best_leaf, cl[1]),
                                   new_id, cr[1])
            out["cand_bin"] = upd(upd(s["cand_bin"], best_leaf, cl[2]),
                                  new_id, cr[2])
            out["cand_dleft"] = upd(upd(s["cand_dleft"], best_leaf, cl[3]),
                                    new_id, cr[3])
            out["cand_lsum"] = upd(upd(s["cand_lsum"], best_leaf, cl[4]),
                                   new_id, cr[4])
            out["cand_rsum"] = upd(upd(s["cand_rsum"], best_leaf, cl[5]),
                                   new_id, cr[5])
            out["cand_member"] = upd(upd(s["cand_member"], best_leaf, cl[6]),
                                     new_id, cr[6])
            write_split_records(
                out, node=node, leaf=best_leaf, new_id=new_id, feat=feat,
                thr=thr, f_nan_bin=f_nan_bin, dt_bits=dt_bits, gain=bgain,
                internal_value=leaf_output(psum_[0], psum_[1], sp),
                internal_weight=psum_[1], internal_count=psum_[2],
                left_child=left_child, right_child=right_child,
                member=member, active=do)
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
            if use_ic:
                out["leaf_path"] = upd(upd(s["leaf_path"], best_leaf,
                                           child_path), new_id, child_path)
            out["num_leaves"] = s["num_leaves"] + do.astype(jnp.int32)
            # a skipped FORCED split (empty leaf) must not end growth
            out["done"] = jnp.logical_not(do) & (t >= n_forced) \
                if n_forced else jnp.logical_not(do)
            return out

        s = jax.lax.fori_loop(0, L - 1, body, state)

        # ---- reconstruct row_leaf in ORIGINAL row order ----
        # leaf id per position via binary search over the sorted segment
        # starts (an associative_scan forward-fill here took XLA 30+ min to
        # compile at 10.5M rows — searchsorted over the L-element starts
        # compiles in seconds and is one gather per row at runtime).
        # Empty segments (possible when all in-bag rows go one way but the
        # out-of-bag tail doesn't) are parked at start=n so they never
        # cover a position.
        starts = jnp.where((jnp.arange(L) < s["num_leaves"]) &
                           (s["leaf_seg"] > 0), s["leaf_start"], n)
        order = jnp.argsort(starts)
        starts_sorted = starts[order]
        pos = jnp.arange(n, dtype=jnp.int32)
        leaf_of_pos = order[
            jnp.searchsorted(starts_sorted, pos, side="right") - 1
        ].astype(jnp.int32)
        orig = jax.lax.bitcast_convert_type(s["P"][:, G + 8:G + 12],
                                            jnp.int32)
        row_leaf = jnp.zeros((n,), jnp.int32).at[orig].set(leaf_of_pos)

        return GrownTree(
            split_feature=s["split_feature"],
            threshold_bin=s["threshold_bin"],
            nan_bin=s["nan_bin"], cat_member=s["cat_member"],
            decision_type=s["decision_type"],
            left_child=s["left_child"], right_child=s["right_child"],
            split_gain=s["split_gain"], internal_value=s["internal_value"],
            internal_weight=s["internal_weight"],
            internal_count=s["internal_count"], leaf_value=s["leaf_value"],
            leaf_weight=s["leaf_weight"], leaf_count=s["leaf_count"],
            num_leaves=s["num_leaves"], row_leaf=row_leaf,
            **untracked_passes())

    return jax.jit(grow) if jit else grow
