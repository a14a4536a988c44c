"""Histogram construction: the hot op of histogram-based GBDT.

TPU-native replacement for the reference's histogram kernels
(reference: src/io/dense_bin.hpp:18 templated ``ConstructHistogram`` inner
loops — the hottest CPU code; src/treelearner/ocl/histogram256.cl and
src/treelearner/kernels/histogram_16_64_256.cu — the GPU equivalents with
local-memory float atomics).

TPUs have no fast global atomics, so scatter-add is reformulated:

* ``onehot`` — one-hot expansion of bin codes contracted against the
  (grad, hess, count) rows on the MXU: ``(3, N) @ (N, F*B)``.  This is the
  TPU-idiomatic formulation — the histogram becomes a matmul, chunked over
  rows via ``lax.scan`` to bound memory (the one-hot tile lives only inside
  one chunk).  The Pallas kernel in ``histogram_pallas.py`` fuses the one-hot
  materialization into VMEM.
* ``segment`` — flat ``scatter-add`` (XLA lowers to sorted segment sums);
  portable reference path used on CPU and in tests.
* ``packed4`` — joint-nibble scatter for ``max_bin <= 16`` data: a
  feature PAIR shares one byte (two 4-bit codes, the reference
  dense_bin.hpp 4-bit layout), one scatter builds the pair's joint
  256-bin histogram and both 16-bin marginals fall out as cheap sums —
  half the scatter volume, ~2x on the scatter-bound CPU backend
  (PERF.md round 10).  The device analog is the Pallas kernels'
  ``bins_packed`` path (histogram_pallas.pack_bins4).

All accumulation is float32 (like the reference GPU learner's single-precision
``gpu_hist_t``, gpu_tree_learner.h:79; the reference CPU path uses float64 —
``tpu_double_precision_gain`` upgrades gain math, mirroring ``gpu_use_dp``).
Counts ride in channel 2 as float32, exact up to 2^24 rows per chunk.

Layout: histograms are ``(F, B, 3)`` with channels (sum_grad, sum_hess,
count).  The reference's (grad, hess) interleaved layout is bin.h:32
``hist_t``; count is implicit there via hessian when unweighted, explicit
here because TPU f32 hessian sums are not exact counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["build_histogram", "build_histogram_leaves", "histogram_subtract",
           "split_hi_lo"]


def split_hi_lo(v: jnp.ndarray):
    """Split f32 v into (hi, lo) with v == hi + lo and hi exactly
    representable in bf16.  TPU matmuls round f32 operands to bf16 at
    DEFAULT precision; carrying (hi, lo) channels keeps the contraction
    f32-exact at bf16 speed (same trick as the Pallas kernel).  The mask is
    integer ops because XLA folds a bf16 round-trip to zero under jit."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi, v - hi


def _hist_onehot_chunk(bins_chunk: jnp.ndarray, w_chunk: jnp.ndarray,
                       num_bins: int) -> jnp.ndarray:
    """One chunk's histogram via MXU matmul.

    bins_chunk: (n, F) integer codes; w_chunk: (n, 3) f32 weights.
    Returns (F, B, 3) f32.
    """
    n, f = bins_chunk.shape
    onehot = (bins_chunk[:, :, None] ==
              jnp.arange(num_bins, dtype=bins_chunk.dtype)[None, None, :])
    onehot = onehot.reshape(n, f * num_bins).astype(jnp.float32)
    # bf16-exact hi/lo weight channels: the one-hot operand is exact 0/1,
    # so splitting the weights recovers f32-exact sums on the TPU MXU
    g_hi, g_lo = split_hi_lo(w_chunk[:, 0])
    h_hi, h_lo = split_hi_lo(w_chunk[:, 1])
    w6 = jnp.stack([g_hi, g_lo, h_hi, h_lo, w_chunk[:, 2]], axis=0)  # (5, n)
    # (5, n) @ (n, F*B) -> (5, F*B): contraction over rows rides the MXU
    flat = jax.lax.dot_general(
        w6, onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    flat3 = jnp.stack([flat[0] + flat[1], flat[2] + flat[3], flat[4]], axis=0)
    return flat3.T.reshape(f, num_bins, 3)


def _hist_segment_chunk(bins_chunk: jnp.ndarray, w_chunk: jnp.ndarray,
                        num_bins: int) -> jnp.ndarray:
    """Scatter-add formulation (portable; CPU-friendly)."""
    n, f = bins_chunk.shape
    ids = bins_chunk.astype(jnp.int32) + (jnp.arange(f, dtype=jnp.int32) *
                                          num_bins)[None, :]
    flat = jnp.zeros((f * num_bins, 3), dtype=jnp.float32)
    updates = jnp.broadcast_to(w_chunk[:, None, :], (n, f, 3)).reshape(-1, 3)
    flat = flat.at[ids.reshape(-1)].add(updates, mode="drop")
    return flat.reshape(f, num_bins, 3)


def _hist_packed4_chunk(bins_chunk: jnp.ndarray, w_chunk: jnp.ndarray,
                        num_bins: int) -> jnp.ndarray:
    """Joint-nibble scatter formulation for max_bin<=16 data (the XLA
    analog of the reference's 4-bit dense_bin.hpp bins and of the Pallas
    kernels' packed layout).  Feature pairs (2j, 2j+1) share one byte
    (lo | hi<<4); ONE scatter of n*ceil(F/2) updates builds the pairs'
    JOINT 256-bin histograms, and both marginals fall out as cheap
    16-way sums — half the scatter volume of the ``segment`` path, which
    is what the scatter-bound CPU backend pays for."""
    n, f = bins_chunk.shape
    fp = (f + 1) // 2
    lo = bins_chunk[:, 0::2].astype(jnp.int32)
    hi = bins_chunk[:, 1::2].astype(jnp.int32)
    if f % 2:
        # odd F: the last feature pairs with a virtual all-zeros column
        # whose marginal is discarded below
        hi = jnp.concatenate([hi, jnp.zeros((n, 1), jnp.int32)], axis=1)
    ids = (lo | (hi << 4)) + (jnp.arange(fp, dtype=jnp.int32) * 256)[None, :]
    flat = jnp.zeros((fp * 256, 3), dtype=jnp.float32)
    upd = jnp.broadcast_to(w_chunk[:, None, :], (n, fp, 3)).reshape(-1, 3)
    joint = flat.at[ids.reshape(-1)].add(upd, mode="drop")
    joint = joint.reshape(fp, 16, 16, 3)          # [pair, hi bin, lo bin]
    lo_h = joint.sum(axis=1)                      # (fp, 16, 3) even feats
    hi_h = joint.sum(axis=2)                      # (fp, 16, 3) odd feats
    out = jnp.stack([lo_h, hi_h], axis=1).reshape(fp * 2, 16, 3)
    return out[:f, :num_bins, :]


def _auto_impl() -> str:
    from ..utils.backend import default_backend
    return "onehot" if default_backend() == "tpu" else "segment"


@functools.partial(jax.jit, static_argnames=("num_bins", "impl", "rows_per_chunk"))
def build_histogram(bins: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                    mask: jnp.ndarray, *, num_bins: int,
                    impl: str = "auto", rows_per_chunk: int = 0) -> jnp.ndarray:
    """Build per-feature (grad, hess, count) histograms over masked rows.

    Replaces Dataset::ConstructHistograms (src/io/dataset.cpp:1111) +
    Bin::ConstructHistogram (dense_bin.hpp).  ``mask`` is 1.0 for rows in the
    target leaf (and in-bag), 0.0 otherwise — leaf membership masking replaces
    the reference's DataPartition row-index gather, keeping shapes static
    under jit.

    Args:
      bins: (N, F) integer bin codes (uint8/uint16/int32).
      grad, hess: (N,) float32 gradients/hessians.
      mask: (N,) float32 row mask.
      num_bins: static global bin count B.
    Returns:
      (F, B, 3) float32 histogram.
    """
    if impl == "auto":
        impl = _auto_impl()
    n, f = bins.shape
    w = jnp.stack([grad * mask, hess * mask, mask], axis=-1)  # (N, 3)

    if impl == "packed4":
        if num_bins > 16:
            raise ValueError("impl='packed4' requires num_bins <= 16 "
                             f"(got {num_bins}); use segment/onehot")
        chunk_fn = _hist_packed4_chunk
    elif impl == "onehot":
        chunk_fn = _hist_onehot_chunk
    else:
        chunk_fn = _hist_segment_chunk

    if rows_per_chunk <= 0:
        # bound the one-hot tile to ~64 MB f32
        rows_per_chunk = max(256, int((64 << 20) / 4 / max(1, f * num_bins)))
    if n <= rows_per_chunk:
        return chunk_fn(bins, w, num_bins)

    num_chunks = -(-n // rows_per_chunk)
    pad = num_chunks * rows_per_chunk - n
    bins_p = jnp.pad(bins, ((0, pad), (0, 0)))
    w_p = jnp.pad(w, ((0, pad), (0, 0)))  # padded rows have mask 0
    bins_c = bins_p.reshape(num_chunks, rows_per_chunk, f)
    w_c = w_p.reshape(num_chunks, rows_per_chunk, 3)

    def scan_body(acc, chunk):
        b, ww = chunk
        return acc + chunk_fn(b, ww, num_bins), None

    init = jnp.zeros((f, num_bins, 3), dtype=jnp.float32)
    hist, _ = jax.lax.scan(scan_body, init, (bins_c, w_c))
    return hist


@functools.partial(jax.jit, static_argnames=("num_channels", "num_bins",
                                             "impl"))
def build_histogram_leaves(bins: jnp.ndarray, grad: jnp.ndarray,
                           hess: jnp.ndarray, mask: jnp.ndarray,
                           ch: jnp.ndarray, *, num_channels: int,
                           num_bins: int, impl: str = "auto") -> jnp.ndarray:
    """(K, F, B, 3) histograms of K leaf channels in one logical pass.

    Portable counterpart of ``build_histogram_pallas_leaves``: rows carry a
    leaf-channel id ``ch`` in [0, K) (or -1 = skip).  The ``segment`` path
    folds the channel into the scatter index; the ``onehot`` path loops the
    K channels (still one XLA program).  Used by the wave grower
    (learner/wave.py) off-TPU and in tests.
    """
    if impl == "auto":
        impl = _auto_impl()
    if impl == "packed4":
        impl = "segment"  # the joint-nibble trick has no leaf-channel form
    n, f = bins.shape
    k = num_channels
    w = jnp.stack([grad * mask, hess * mask, mask], axis=-1)      # (N, 3)
    if impl == "segment":
        def chunk_hist(bins_c, w_c, ch_c):
            m = bins_c.shape[0]
            ids = (ch_c.astype(jnp.int32)[:, None] * f +
                   jnp.arange(f, dtype=jnp.int32)[None, :]) * num_bins + \
                bins_c.astype(jnp.int32)
            ids = jnp.where(ch_c[:, None] >= 0, ids, k * f * num_bins)
            flat = jnp.zeros((k * f * num_bins, 3), dtype=jnp.float32)
            upd = jnp.broadcast_to(w_c[:, None, :], (m, f, 3)).reshape(-1, 3)
            return flat.at[ids.reshape(-1)].add(
                upd, mode="drop").reshape(k, f, num_bins, 3)

        # bound the (rows, F, 3) updates tensor like build_histogram does
        rows_per_chunk = max(256, int((64 << 20) / 12 / max(1, f)))
        if n <= rows_per_chunk:
            return chunk_hist(bins, w, ch)
        num_chunks = -(-n // rows_per_chunk)
        pad = num_chunks * rows_per_chunk - n
        bins_p = jnp.pad(bins, ((0, pad), (0, 0)))
        w_p = jnp.pad(w, ((0, pad), (0, 0)))
        ch_p = jnp.pad(ch, (0, pad), constant_values=-1)

        def scan_body(acc, c):
            b_, w_, c_ = c
            return acc + chunk_hist(b_, w_, c_), None

        init = jnp.zeros((k, f, num_bins, 3), dtype=jnp.float32)
        hist, _ = jax.lax.scan(
            scan_body, init,
            (bins_p.reshape(num_chunks, rows_per_chunk, f),
             w_p.reshape(num_chunks, rows_per_chunk, 3),
             ch_p.reshape(num_chunks, rows_per_chunk)))
        return hist

    def one(c):
        m = mask * (ch == c).astype(jnp.float32)
        return build_histogram(bins, grad, hess, m, num_bins=num_bins,
                               impl=impl)

    return jnp.stack([one(c) for c in range(k)])


def histogram_subtract(parent: jnp.ndarray, child: jnp.ndarray) -> jnp.ndarray:
    """The histogram subtraction trick: sibling = parent - child
    (reference serial_tree_learner.cpp:311-320, FeatureHistogram::Subtract)."""
    return parent - child
