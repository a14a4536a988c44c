"""Chunked wave grower: leaf-wise growth over host-resident row chunks.

The training half of the out-of-core ingest subsystem (ROADMAP item 2):
the wave grower's full-data histogram passes (learner/wave.py) replicated
one level up the memory hierarchy — host RAM -> HBM instead of HBM ->
VMEM (PR 8's DMA pipeline pattern).  Each wave's (W, F, B, 3) histogram
batch is accumulated **chunk by chunk**: chunk *i+1*'s ``device_put``
(bins + weight lanes) is issued before chunk *i*'s histogram kernel is
consumed, so the host->HBM copy overlaps the accumulation the same way
the Pallas kernels overlap HBM->VMEM DMA with the MXU contraction.  HBM
holds a bounded ring of two chunk buffers plus the wave state — the
``ingest/chunk_pipeline`` MemoryBudget (ingest/stream.py) has no
total-rows term, and ``lint-mem`` checks it.

**Exactness.** The grower mirrors ``learner/wave.py``'s traced wave body
for its supported envelope (numeric non-EFB features, no monotone/
interaction/bynode/extra-trees/CEGB/forced splits, spec ramp and the
exact endgame off — the wave taper handles the tail).  With
``use_quantized_grad=true`` (the numerically sound mode at out-of-core
scale — f32 histogram counts stop being exact past 2^24 rows anyway) and
``stochastic_rounding=false``, every per-(leaf, feature, bin) channel sum
is an exact int32 regardless of accumulation order, so streamed training
is **bit-identical** to an in-core run of the same configuration
(tests/test_ingest_train.py asserts model-text equality).  The f32 path
is supported but chunk-sums f32 histograms, which reassociates the adds —
trees match in structure and to f32 tolerance, not bitwise.

Per-row state (score, grad/hess, row_leaf, bag mask, quantized weight
lanes) lives on the HOST (~20 B/row + the on-disk binned cache); only
per-chunk slices ever enter HBM.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..learner.serial import GrownTree, local_best_candidate
from ..learner.wave import Q_WAVE_SIZE, WAVE_SIZE, wave_taper_k
from ..models.tree import DEFAULT_LEFT_MASK, MISSING_NAN
from ..ops.histogram import build_histogram_leaves
from ..ops.quantize import dequant_scales, quantize_wch
from ..ops.split import BIG, NEG_INF, SplitParams, leaf_output
from ..telemetry.metrics import default_registry
from ..telemetry.trace import span

__all__ = ["ChunkedWaveGrower", "StreamedEnvelopeError"]


class StreamedEnvelopeError(ValueError):
    """The requested configuration is outside the chunked grower's
    envelope; train in-core (``tpu_ingest_mode=hbm``) instead."""


def _dev(x):
    return jax.device_put(x)


class ChunkedWaveGrower:
    """One-tree grower over a chunk store.

    ``chunks`` is any sequence-like with ``num_chunks()``,
    ``binned_chunk(i)`` -> (m, F) uint8 and ``chunk_bounds(i)``; the
    per-row arrays (grad/hess/mask/row_leaf) are host numpy, sliced and
    uploaded per chunk.
    """

    def __init__(self, *, num_leaves: int, num_features: int, max_bins: int,
                 max_depth: int, split_params: SplitParams,
                 num_bins: np.ndarray, has_nan: np.ndarray,
                 hist_impl: str = "segment", quantized: bool = False,
                 gq_max: int = 127, hq_max: int = 127,
                 wave_size: int = 0, interpret: Optional[bool] = None,
                 pipeline: Optional[str] = None) -> None:
        if split_params.any_cat:
            raise StreamedEnvelopeError(
                "chunked streamed training supports numeric features only")
        if max_bins > 255:
            raise StreamedEnvelopeError(
                "chunked streamed training requires max_bin <= 255")
        self.L = int(num_leaves)
        self.F = int(num_features)
        self.B = int(max_bins)
        self.max_depth = int(max_depth)
        self.sp = split_params
        self.quantized = bool(quantized)
        self.gq_max, self.hq_max = int(gq_max), int(hq_max)
        self.hist_impl = hist_impl
        self.pallas = hist_impl == "pallas"
        self.interpret = interpret
        self.pipeline = pipeline
        ch_cap = Q_WAVE_SIZE if quantized else WAVE_SIZE
        self.W = max(1, min(int(wave_size) or ch_cap, ch_cap, self.L - 1))
        self.rl_dtype = np.uint8 if self.L <= 256 else np.int32
        self.num_bins = jnp.asarray(num_bins, jnp.int32)
        self.has_nan = jnp.asarray(has_nan, jnp.bool_)
        self.monotone = jnp.zeros((self.F,), jnp.int32)
        self._head_fn = jax.jit(self._head)
        self._tail_fn = jax.jit(self._tail)
        self._chunk_fn = jax.jit(self._chunk_step)
        self._root_chunk_fn = jax.jit(self._root_chunk)
        self._root_state_fn = jax.jit(self._root_state)
        self.hist_dtype = jnp.int32 if quantized else jnp.float32
        reg = default_registry()
        self._h2d = reg.counter("ingest_train_h2d_bytes_total",
                                "host->HBM bytes streamed by chunked "
                                "training")
        self._passes = reg.counter("ingest_train_hist_passes_total",
                                   "chunk-accumulated full-data histogram "
                                   "passes")

    # -- per-chunk weight lanes ----------------------------------------------
    def _weights(self, grad_c, hess_c, mask_c, scales):
        """Device weight operands for one chunk: quantized int8 lanes,
        the raw triple for the Pallas weight packer, or the f32
        (gm, hm, cnt) triple for the XLA paths — identical elementwise
        math to the in-core grower's."""
        if self.quantized:
            g_scale, h_scale = scales
            return quantize_wch(grad_c, hess_c, mask_c, g_scale, h_scale,
                                jax.random.PRNGKey(0), gq_max=self.gq_max,
                                hq_max=self.hq_max, stochastic=False)
        if self.pallas:
            # pack_weights8 masks internally, exactly like the in-core
            # wave grower's w8 = pack_weights8(grad, hess, bag_mask)
            return grad_c, hess_c, mask_c
        gm = (grad_c * mask_c).astype(jnp.float32)
        hm = (hess_c * mask_c).astype(jnp.float32)
        cnt = (mask_c > 0).astype(jnp.float32)
        return gm, hm, cnt

    def _chunk_hist(self, bins_c, w, ch):
        """One chunk's (W, F, B, 3) channel histograms — exact int32 when
        quantized (chunk accumulation order cannot change the sums)."""
        if self.pallas:
            from ..ops.histogram_pallas import (
                build_histogram_pallas_leaves,
                build_histogram_pallas_leaves_q8, pack_weights8)
            xt = jnp.swapaxes(bins_c, 0, 1).astype(jnp.uint8)
            if self.quantized:
                h = build_histogram_pallas_leaves_q8(
                    xt, w, ch.astype(jnp.int8), num_bins=self.B,
                    interpret=self.interpret, pipeline=self.pipeline)
            else:
                w8 = pack_weights8(w[0], w[1], w[2])   # raw grad/hess/mask
                h = build_histogram_pallas_leaves(
                    xt, w8, ch.astype(jnp.int8), num_bins=self.B,
                    interpret=self.interpret, pipeline=self.pipeline)
            return h[:self.W]
        if self.quantized:
            h = build_histogram_leaves(
                bins_c, w[0].astype(jnp.float32), w[1].astype(jnp.float32),
                w[2].astype(jnp.float32), ch, num_channels=self.W,
                num_bins=self.B, impl=self.hist_impl)
            return jnp.round(h).astype(jnp.int32)
        return build_histogram_leaves(
            bins_c, w[0], w[1], w[2], ch, num_channels=self.W,
            num_bins=self.B, impl=self.hist_impl)

    # -- jitted pieces -------------------------------------------------------
    def _root_chunk(self, acc, acc_sum, bins_c, grad_c, hess_c, mask_c,
                    scales):
        """Root pass over one chunk: accumulate channel-0 histograms and
        (f32 path) the row-reduction root sums."""
        w = self._weights(grad_c, hess_c, mask_c, scales)
        ch = jnp.zeros((bins_c.shape[0],), jnp.int32)
        h = self._chunk_hist(bins_c, w, ch)
        if self.quantized:
            return acc + h[:1], acc_sum
        # f32 root sums from the raw chunk operands (the in-core
        # root_sum's row reductions, chunk-partial)
        gm = (grad_c * mask_c).astype(jnp.float32)
        hm = (hess_c * mask_c).astype(jnp.float32)
        part = jnp.stack([jnp.sum(gm), jnp.sum(hm),
                          jnp.sum((mask_c > 0).astype(jnp.float32))])
        return acc + h[:1], acc_sum + part

    def _root_state(self, root_hist1, root_sum_acc, feature_mask, qscales):
        """Initial wave state from the accumulated root pass — mirrors
        learner/wave.py's non-spec root block."""
        L, B, W = self.L, self.B, self.W
        sp = self.sp
        root_hist = root_hist1[0]
        if self.quantized:
            # root totals from the exact integer histogram (any feature's
            # bins sum to the total), like the in-core quantized root
            root_sum = self._dq(root_hist1[:, 0].sum(axis=1), qscales)[0]
        else:
            root_sum = root_sum_acc
        root_hist_f = self._dq(root_hist, qscales) if self.quantized \
            else root_hist
        root_bound = jnp.asarray([-BIG, BIG], jnp.float32)
        root_out = leaf_output(root_sum[0], root_sum[1], sp)
        cand = local_best_candidate(
            root_hist_f, root_sum, self.num_bins,
            jnp.zeros((self.F,), jnp.bool_), self.has_nan, feature_mask,
            sp, self.monotone, root_bound, jnp.asarray(0, jnp.int32),
            None, None, root_out)
        state = {
            "leaf_sum": jnp.zeros((L, 3), jnp.float32).at[0].set(root_sum),
            "leaf_depth": jnp.zeros((L,), jnp.int32),
            "cand_gain": jnp.full((L,), NEG_INF,
                                  jnp.float32).at[0].set(cand[0]),
            "cand_feat": jnp.zeros((L,), jnp.int32).at[0].set(cand[1]),
            "cand_bin": jnp.zeros((L,), jnp.int32).at[0].set(cand[2]),
            "cand_dleft": jnp.zeros((L,), jnp.bool_).at[0].set(cand[3]),
            "cand_lsum": jnp.zeros((L, 3), jnp.float32).at[0].set(cand[4]),
            "cand_rsum": jnp.zeros((L, 3), jnp.float32).at[0].set(cand[5]),
            "cand_member": jnp.zeros((L, B), jnp.bool_).at[0].set(cand[6]),
            "hists": jnp.zeros((L, self.F, B, 3),
                               self.hist_dtype).at[0].set(root_hist),
            "split_feature": jnp.full((L - 1,), -1, jnp.int32),
            "threshold_bin": jnp.zeros((L - 1,), jnp.int32),
            "nan_bin": jnp.full((L - 1,), -1, jnp.int32),
            "cat_member": jnp.zeros((L - 1, B), jnp.bool_),
            "decision_type": jnp.zeros((L - 1,), jnp.int32),
            "left_child": jnp.zeros((L - 1,), jnp.int32),
            "right_child": jnp.zeros((L - 1,), jnp.int32),
            "split_gain": jnp.zeros((L - 1,), jnp.float32),
            "internal_value": jnp.zeros((L - 1,), jnp.float32),
            "internal_weight": jnp.zeros((L - 1,), jnp.float32),
            "internal_count": jnp.zeros((L - 1,), jnp.float32),
            "leaf_value": jnp.zeros((L,), jnp.float32).at[0].set(root_out),
            "leaf_weight": jnp.zeros((L,),
                                     jnp.float32).at[0].set(root_sum[1]),
            "leaf_count": jnp.zeros((L,),
                                    jnp.float32).at[0].set(root_sum[2]),
            "num_leaves": jnp.asarray(1, jnp.int32),
            "done": jnp.asarray(False),
            "hist_passes": jnp.asarray(1, jnp.int32),
        }
        return state

    def _dq(self, h, qscales):
        """Quantized int32 channel sums -> f32 (per-tree scales)."""
        return h.astype(jnp.float32) * qscales

    def _head(self, s):
        """Wave-head selection — the top-k + taper block of the in-core
        body, producing the commit table the chunk passes consume."""
        L, W = self.L, self.W
        jarange = jnp.arange(W, dtype=jnp.int32)
        nl0 = s["num_leaves"]
        k_eff = wave_taper_k(L - nl0, W)
        vals, sel_leaves = jax.lax.top_k(s["cand_gain"], W)
        sel = (vals > 0) & (jarange < k_eff)
        feat = s["cand_feat"][sel_leaves]
        thr = s["cand_bin"][sel_leaves]
        dleft = s["cand_dleft"][sel_leaves]
        lsum = s["cand_lsum"][sel_leaves]
        rsum = s["cand_rsum"][sel_leaves]
        member = s["cand_member"][sel_leaves]
        psum_ = s["leaf_sum"][sel_leaves]
        prefix = jnp.cumsum(sel.astype(jnp.int32))
        total_new = prefix[-1]
        new_ids = nl0 + prefix - 1
        node_ids = (nl0 - 1) + prefix - 1
        left_smaller = lsum[:, 2] <= rsum[:, 2]
        fnan = self.has_nan[feat]
        f_nan_bin = jnp.where(fnan, self.num_bins[feat] - 1, -1)
        return {"vals": vals, "sel_leaves": sel_leaves, "sel": sel,
                "feat": feat, "thr": thr, "dleft": dleft, "lsum": lsum,
                "rsum": rsum, "member": member, "psum": psum_,
                "new_ids": new_ids, "node_ids": node_ids,
                "left_smaller": left_smaller, "fnan": fnan,
                "f_nan_bin": f_nan_bin, "total_new": total_new}

    def _chunk_step(self, acc, bins_c, rl_c, grad_c, hess_c, mask_c,
                    head, scales):
        """One chunk of one wave: the fused row-update + smaller-child
        histogram accumulation (the in-core body's row_leaf/ch update and
        ``hist_waves(ch)``, restricted to this chunk's rows, with the
        accumulator carried across chunks)."""
        w = self._weights(grad_c, hess_c, mask_c, scales)
        sel, feat = head["sel"], head["feat"]
        thr, dleft = head["thr"], head["dleft"]
        f_nan_bin = head["f_nan_bin"]
        left_smaller = head["left_smaller"]
        sel_leaves, new_ids = head["sel_leaves"], head["new_ids"]
        if self.pallas:
            from ..ops.histogram_pallas import wave_row_update_pallas
            xt = jnp.swapaxes(bins_c, 0, 1).astype(jnp.uint8)
            cols_w = jnp.take(xt, feat, axis=0)
            tab = jnp.stack([
                thr, f_nan_bin, dleft.astype(jnp.int32),
                left_smaller.astype(jnp.int32), sel_leaves, new_ids,
                sel.astype(jnp.int32), jnp.zeros_like(thr)])
            rl_new, ch = wave_row_update_pallas(
                cols_w, rl_c, tab, interpret=self.interpret,
                pipeline=self.pipeline)
            rl_new = rl_new.astype(rl_c.dtype)
        else:
            # the in-core body's vectorized XLA row update (_upd_block),
            # restricted to numeric non-EFB shapes — elementwise per row,
            # so per-chunk evaluation is bit-identical to the full pass
            xt = jnp.swapaxes(bins_c, 0, 1)
            cols_w = jnp.take(xt, feat, axis=0)            # (W, m)
            thr_c = thr.astype(bins_c.dtype)[:, None]
            nan_c = jnp.where(f_nan_bin < 0, 255,
                              f_nan_bin).astype(bins_c.dtype)[:, None]
            sel_c = sel_leaves.astype(rl_c.dtype)
            num_go = jnp.where(cols_w == nan_c, dleft[:, None],
                               cols_w <= thr_c)
            match = sel[:, None] & (rl_c[None, :] == sel_c[:, None])
            has = jnp.any(match, axis=0)
            jhit = jnp.argmax(match, axis=0)
            go = jnp.take_along_axis(num_go, jhit[None, :], axis=0)[0]
            ch = jnp.where(has & (go == left_smaller[jhit]),
                           jhit.astype(jnp.int8), jnp.int8(-1))
            rl_new = jnp.where(has & jnp.logical_not(go),
                               new_ids[jhit].astype(rl_c.dtype), rl_c)
        h = self._chunk_hist(bins_c, w, ch)
        return acc + h, rl_new

    def _tail(self, s, head, hist_small, feature_mask, qscales):
        """Post-accumulation half of the in-core wave body: subtraction,
        children candidate scans, state scatter + node records."""
        L, W, F, B = self.L, self.W, self.F, self.B
        sp = self.sp
        sel, sel_leaves = head["sel"], head["sel_leaves"]
        feat, thr, dleft = head["feat"], head["thr"], head["dleft"]
        lsum, rsum, psum_ = head["lsum"], head["rsum"], head["psum"]
        member = head["member"]
        new_ids, node_ids = head["new_ids"], head["node_ids"]
        left_smaller = head["left_smaller"]
        fnan, f_nan_bin = head["fnan"], head["f_nan_bin"]
        vals, total_new = head["vals"], head["total_new"]
        nl0 = s["num_leaves"]

        parents = s["hists"][sel_leaves]
        hist_big = parents - hist_small
        ls4 = left_smaller[:, None, None, None]
        hist_l = jnp.where(ls4, hist_small, hist_big)
        hist_r = jnp.where(ls4, hist_big, hist_small)

        out_l = leaf_output(lsum[:, 0], lsum[:, 1], sp)
        out_r = leaf_output(rsum[:, 0], rsum[:, 1], sp)

        child_depth = s["leaf_depth"][sel_leaves] + 1
        hists2 = jnp.concatenate([hist_l, hist_r])
        sums2 = jnp.concatenate([lsum, rsum])
        hf2 = self._dq(hists2, qscales) if self.quantized else hists2
        depth2 = jnp.concatenate([child_depth, child_depth])
        lv2 = jnp.concatenate([out_l, out_r])
        fm2 = jnp.broadcast_to(feature_mask, (2 * W, F))
        ic = jnp.zeros((F,), jnp.bool_)

        # monotone bounds stay None: use_mc is statically outside the
        # chunked envelope (the in-core body passes None there too)
        def one(h, s_, d, po, fm_):
            return local_best_candidate(
                h, s_, self.num_bins, ic, self.has_nan, fm_, sp,
                self.monotone, None, d, None, None, po)

        cands = jax.vmap(one)(hf2, sums2, depth2, lv2, fm2)
        depth_ok = jnp.logical_or(self.max_depth <= 0,
                                  child_depth < self.max_depth)
        dok2 = jnp.concatenate([depth_ok, depth_ok])
        cg = jnp.where(dok2 & jnp.concatenate([sel, sel]), cands[0],
                       NEG_INF)

        idx_l = jnp.where(sel, sel_leaves, L)
        idx_r = jnp.where(sel, new_ids, L)
        idx2 = jnp.concatenate([idx_l, idx_r])

        def sc2(arr, val2):
            return arr.at[idx2].set(val2, mode="drop")

        out = dict(s)
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
        out["leaf_value"] = sc2(s["leaf_value"], lv2)
        out["leaf_weight"] = sc2(s["leaf_weight"], sums2[:, 1])
        out["leaf_count"] = sc2(s["leaf_count"], sums2[:, 2])

        nidx = jnp.where(sel, node_ids, L - 1)
        dt_bits = (jnp.where(dleft, DEFAULT_LEFT_MASK, 0) |
                   jnp.where(fnan, MISSING_NAN, 0)).astype(jnp.int32)

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
        out["hist_passes"] = s["hist_passes"] + 1
        return out

    # -- host-driven tree growth ---------------------------------------------
    def grow(self, store, grad: np.ndarray, hess: np.ndarray,
             mask: np.ndarray, feature_mask: Optional[np.ndarray] = None
             ) -> tuple:
        """Grow one tree.  Returns (host GrownTree, per-chunk row_leaf
        list).  ``store`` is a StreamedDataset (or equivalent)."""
        nc = store.num_chunks()
        fm = jnp.asarray(feature_mask if feature_mask is not None
                         else np.ones(self.F, bool))
        pad_to = store.chunk_rows

        def chunk_arrays(i):
            lo, hi = store.chunk_bounds(i)
            m = hi - lo
            bins = np.asarray(store.binned_chunk(i))
            g = grad[lo:hi].astype(np.float32)
            h = hess[lo:hi].astype(np.float32)
            mk = mask[lo:hi].astype(np.float32)
            if m < pad_to:
                # the last short block pads to the fixed chunk shape (one
                # compiled program per config); padded rows carry zero
                # weight lanes and cannot touch the histograms
                pad = pad_to - m
                bins = np.pad(bins, ((0, pad), (0, 0)))
                g = np.pad(g, (0, pad))
                h = np.pad(h, (0, pad))
                mk = np.pad(mk, (0, pad))
            self._h2d.inc(bins.nbytes + g.nbytes + h.nbytes + mk.nbytes)
            return (_dev(bins), _dev(g), _dev(h), _dev(mk)), m

        def prefetched():
            """Double-buffered chunk upload: issue chunk i+1's
            device_put before chunk i is consumed."""
            nxt = chunk_arrays(0)
            for i in range(nc):
                cur = nxt
                if i + 1 < nc:
                    nxt = chunk_arrays(i + 1)
                yield i, cur

        # ---- quantized scales: one streaming host max pass --------------
        # max is exact under any chunking, and numpy's f32 multiply is
        # the same IEEE op the in-core jnp.max(|grad*mask|) reduces over,
        # so the derived scales match the in-core tree's bit for bit.
        if self.quantized:
            gmax = 0.0
            hmax = 0.0
            for i in range(nc):
                lo, hi = store.chunk_bounds(i)
                g32 = grad[lo:hi].astype(np.float32, copy=False)
                h32 = hess[lo:hi].astype(np.float32, copy=False)
                m32 = mask[lo:hi].astype(np.float32, copy=False)
                gmax = max(gmax, float(np.max(np.abs(g32 * m32))))
                hmax = max(hmax, float(np.max(h32 * m32)))
            g_scale = jnp.maximum(jnp.float32(gmax),
                                  jnp.float32(1e-30)) / self.gq_max
            h_scale = jnp.maximum(jnp.float32(hmax),
                                  jnp.float32(1e-30)) / self.hq_max
            scales = (g_scale, h_scale)
            qscales = dequant_scales(g_scale, h_scale)
        else:
            scales = (jnp.float32(1.0), jnp.float32(1.0))
            qscales = jnp.ones((3,), jnp.float32)

        # ---- root pass --------------------------------------------------
        with span("ingest/train/root_pass"):
            acc = jnp.zeros((1, self.F, self.B, 3), self.hist_dtype)
            acc_sum = jnp.zeros((3,), jnp.float32)
            for _, ((b, g, h, mk), _m) in prefetched():
                acc, acc_sum = self._root_chunk_fn(acc, acc_sum, b, g, h,
                                                   mk, scales)
            state = self._root_state_fn(acc, acc_sum, fm, qscales)
        self._passes.inc()

        rl_chunks: List[np.ndarray] = [
            np.zeros(store.chunk_bounds(i)[1] - store.chunk_bounds(i)[0],
                     self.rl_dtype) for i in range(nc)]

        # ---- wave loop --------------------------------------------------
        while True:
            done = bool(jax.device_get(state["done"]))
            nl = int(jax.device_get(state["num_leaves"]))
            if done or nl >= self.L:
                break
            head = self._head_fn(state)
            with span("ingest/train/wave_pass"):
                acc = jnp.zeros((self.W, self.F, self.B, 3),
                                self.hist_dtype)
                for i, ((b, g, h, mk), m) in prefetched():
                    rl_c = rl_chunks[i]
                    if len(rl_c) < pad_to:
                        rl_c = np.pad(rl_c, (0, pad_to - len(rl_c)))
                    self._h2d.inc(rl_c.nbytes)   # the row_leaf ring leg
                    acc, rl_new = self._chunk_fn(acc, b, _dev(rl_c), g, h,
                                                 mk, head, scales)
                    rl_chunks[i] = np.asarray(rl_new)[:m]
            state = self._tail_fn(state, head, acc, fm, qscales)
            self._passes.inc()

        host = jax.device_get(state)
        grown = GrownTree(
            split_feature=host["split_feature"],
            threshold_bin=host["threshold_bin"],
            nan_bin=host["nan_bin"], cat_member=host["cat_member"],
            decision_type=host["decision_type"],
            left_child=host["left_child"],
            right_child=host["right_child"],
            split_gain=host["split_gain"],
            internal_value=host["internal_value"],
            internal_weight=host["internal_weight"],
            internal_count=host["internal_count"],
            leaf_value=host["leaf_value"],
            leaf_weight=host["leaf_weight"],
            leaf_count=host["leaf_count"],
            num_leaves=host["num_leaves"],
            row_leaf=np.zeros((0,), np.int32),
            hist_passes=host["hist_passes"],
            # every streamed pass after the root's is a wave: this grower
            # has neither a ramp nor an endgame
            wave_passes=host["hist_passes"] - 1,
            endgame_passes=np.int32(0), ramp_committed=np.int32(0),
            hist_rows_contracted=np.zeros((1, 2), np.int32),
            pass_log=np.zeros((1, 0, 6), np.int32),
            ramp_sample=np.zeros((1, 2), np.int32))
        return grown, rl_chunks
