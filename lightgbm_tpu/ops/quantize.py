"""Gradient quantization for int8 histogram training.

TPU-native analog of the reference's gradient discretizer
(reference: src/treelearner/gradient_discretizer.cpp DiscretizeGradients,
include/LightGBM/config.h use_quantized_grad / num_grad_quant_bins /
quant_train_renew_leaf / stochastic_rounding): per-tree linear scales map
gradients to signed and hessians to unsigned integer levels with
stochastic rounding, histograms accumulate exact int32 sums on the MXU
(ops/histogram_pallas.py build_histogram_pallas_leaves_q8), and split
gains are computed on the dequantized sums.  Differences from the
reference, by design:

* levels ride int8 MXU lanes, so up to 127 gradient levels are free —
  the reference's default ``num_grad_quant_bins=4`` is honored but any
  value up to 254 is accepted (we clamp levels to the int8 range);
* the count channel is an exact int32 row count (the reference packs
  grad/hess as int16 pairs and renormalizes; we keep three lanes).

**Where an int32 sum can wrap, and what keeps it exact.**  The leaf
kernels add one int32 per (leaf channel, feature, bin, lane) over all the
rows of a pass.  A product is at most ``max(gq_max, hq_max)`` = 127 in
size, so a sum is exact while the rows of ONE leaf that fall in ONE bin of
one feature, times 127, stay within 2^31 - 1: 16,909,320 rows a shard.
(The count lane is exact to 2^31 rows; the bin sums of a feature added up
for a leaf's totals obey the same bound with "one bin" read as "the whole
leaf".)  Rows a shard alone do not say whether that can happen: 21.25M
dense standard-normal columns put 83K rows in a bin, while 45.84M rows
with a column that is 77% missing put 35M in one, and the first tree of a
binary objective gives every row the top hessian level.  So the learner
takes the bound from what it knows when it builds the grower
(:func:`hist_acc_rows`: rows a shard, the levels, and the largest share of
the binning sample that one bin of one feature holds, times four for the
sample's error; a mapper of unknown origin counts as share 1) and, where
the bound can be passed, has every pass accumulate at most that many rows
into one int32: the kernels sum the pass's row blocks in segments, and the
segments' exact int32 sums are added as two limbs (:func:`hist_limbs`:
low 16 bits and the rest, five int32 lanes a bin: g_lo, h_lo, count,
g_hi, h_hi).  Limbs add, subtract (parent - child) and cross a mesh like
the sums themselves; only the step to float (:func:`dequant_limbs`) puts
them together: value = hi * 65536 + lo, exact in the integers, rounded
once to float32 as the narrow form's ``astype`` is.  Same levels, no
saturation, no float accumulation; a grower whose bound cannot be passed
traces the narrow program unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["quant_levels", "quantize_wch", "dequant_scales",
           "INT32_ACC_MAX", "hist_acc_rows", "hist_limbs", "dequant_limbs"]

INT32_ACC_MAX = (1 << 31) - 1
# the binning sample says how full a data set's fullest bin is; its error
# is covered by this factor (a bin that holds over a third of the rows
# would have to read under a twelfth in the sample to be missed)
_SHARE_MARGIN = 4.0


def hist_acc_rows(rows: int, gq_max: int, hq_max: int,
                  max_bin_share: float = 1.0, quantum: int = 4096) -> int:
    """Rows the q8 kernels may add into one int32, or 0 where one
    accumulation over all ``rows`` (a shard's) cannot wrap: the most rows
    that one (leaf, bin) can hold is ``rows`` times the fullest bin's
    share (``max_bin_share`` of the binning sample, times the margin, at
    most 1), and each adds at most ``max(gq_max, hq_max)``.  The result is
    a multiple of ``quantum`` (the kernels' row block)."""
    level = max(int(gq_max), int(hq_max), 1)
    share = min(1.0, max(float(max_bin_share), 0.0) * _SHARE_MARGIN)
    if int(rows * share + 1) * level <= INT32_ACC_MAX:
        return 0
    return max(quantum, INT32_ACC_MAX // level // quantum * quantum)


def hist_limbs(parts) -> jnp.ndarray:
    """Exact sum of int32 histograms ``parts`` (each (..., 3): sum g_q,
    sum h_q, count) as (..., 5) int32 limbs [g_lo, h_lo, count, g_hi,
    h_hi] with sum = hi * 65536 + lo.  Each part must be exact on its own;
    their sum need not fit an int32 (the count must)."""
    lo = sum(p & 0xFFFF for p in parts)
    hi = sum(p >> 16 for p in parts)
    cnt = lo[..., 2:] + (hi[..., 2:] << 16)
    return jnp.concatenate([lo[..., :2], cnt, hi[..., :2]], axis=-1)


def dequant_limbs(h: jnp.ndarray) -> jnp.ndarray:
    """(..., 5) int32 limbs -> (..., 3) float32 integer-valued sums."""
    f = h.astype(jnp.float32)
    gh = f[..., 3:] * jnp.float32(65536.0) + f[..., :2]
    return jnp.concatenate([gh, f[..., 2:3]], axis=-1)


def quant_levels(num_grad_quant_bins: int) -> tuple:
    """(gq_max, hq_max) integer level bounds for a quant-bin count.

    Gradients are symmetric in [-gq_max, gq_max]; hessians (non-negative)
    in [0, hq_max].  Both clamp to the int8 payload range."""
    qb = max(2, int(num_grad_quant_bins))
    return max(1, min(qb // 2, 127)), max(1, min(qb, 127))


@functools.partial(jax.jit, static_argnames=("gq_max", "hq_max",
                                             "stochastic"))
def quantize_wch(grad: jnp.ndarray, hess: jnp.ndarray, bag_mask: jnp.ndarray,
                 g_scale: jnp.ndarray, h_scale: jnp.ndarray,
                 key: jnp.ndarray, *, gq_max: int, hq_max: int,
                 stochastic: bool = True) -> jnp.ndarray:
    """(8, N) int8 FEATURE-MAJOR weight rows [g_q, h_q, count, 0, ...].

    ``g_scale``/``h_scale`` are the per-tree dequantization scales
    (g ~= g_q * g_scale); callers compute them from (cross-shard) maxima
    so data-parallel shards quantize identically.  The result is static
    for the whole tree — the per-wave leaf channel rides a separate
    (N,) int8 kernel input, so this buffer is never rewritten.
    Stochastic rounding ``floor(x + u)`` is unbiased for either sign;
    with ``stochastic=False`` it degrades to round-half-up.
    """
    n = grad.shape[0]
    gm = (grad * bag_mask) / g_scale
    hm = (hess * bag_mask) / h_scale
    if stochastic:
        ug = jax.random.uniform(jax.random.fold_in(key, 0), (n,))
        uh = jax.random.uniform(jax.random.fold_in(key, 1), (n,))
    else:
        ug = uh = jnp.float32(0.5)
    g_q = jnp.clip(jnp.floor(gm + ug), -gq_max, gq_max).astype(jnp.int8)
    h_q = jnp.clip(jnp.floor(hm + uh), 0, hq_max).astype(jnp.int8)
    cnt = (bag_mask > 0).astype(jnp.int8)
    z = jnp.zeros_like(cnt)
    return jnp.stack([g_q, h_q, cnt, z, z, z, z, z], axis=0)


def dequant_scales(g_scale, h_scale):
    """(3,) f32 multiplier turning int32 channel sums into f32 sums."""
    return jnp.stack([g_scale, h_scale, jnp.float32(1.0)])
