"""Pallas TPU histogram kernel — the hot op, on the MXU.

TPU-native analog of the reference's device histogram kernels
(reference: src/treelearner/ocl/histogram256.cl:476-505 local-memory float
atomics; src/treelearner/kernels/histogram_16_64_256.cu:23-341; CPU inner
loops src/io/dense_bin.hpp:18-52).  TPUs have no fast atomics, so scatter-add
is reformulated as a one-hot contraction — but unlike the XLA ``onehot`` path
(ops/histogram.py), the one-hot tile here never leaves VMEM:

  for each row-block (sequential grid) and each feature f:
      onehot = (bins[f, block] == iota(B))        # (B, R) bf16, in VMEM only
      hist[f] += onehot @ w_block                  # MXU, f32 accumulation

Precision: the MXU contracts bf16 operands into f32.  The 0/1 one-hot is
exact in bf16; gradients/hessians are carried as **bf16 hi+lo pairs**
(value = hi + lo, lo = value - f32(hi)), so each product is exact to f32
precision and the result matches a f32 matmul — the extra channels are free
because the MXU lane dimension is padded to 128 anyway (we use 5 of 128:
g_hi, g_lo, h_hi, h_lo, count).  This beats the reference GPU learner's
plain-f32 ``gpu_hist_t`` (gpu_tree_learner.h:79) in exactness per cycle.

Layout contract: bins arrive **feature-major** ``(F, N)`` so each feature's
row-block is a contiguous lane vector; N must be a multiple of the row block
R (the Dataset pads device uploads; masked rows carry w=0 and contribute
nothing).  Output is ``(F, B, 3)`` f32 (sum_grad, sum_hess, count).

MXU cycle floor: F * ceil(B/128) * N K-slices per full build — at Higgs
scale (10.5M x 28, B=256) ~0.1 s/full build; the tree grower's subtraction
trick (ops/histogram.py histogram_subtract) keeps builds to ~4 full-N
equivalents per 255-leaf tree.

Kernel v2 (PERF.md round 10): every entry point carries a ``pipeline``
switch — ``"dma"`` (the on-TPU default) streams the bins +
packed-weight row blocks HBM->VMEM through explicitly double-buffered
``make_async_copy`` pairs that overlap the contraction (the kernels
were measured 1.43x above the MXU floor on the implicit fetch; this
targets that residue), ``"blockspec"`` keeps the v1 implicit
per-grid-step fetch for A/B re-probing (and is the default under
off-TPU interpretation, where DMA machinery is emulation overhead).  When ``max_bin <= PACK4_MAX_BINS`` the bins may arrive
nibble-PACKED (``pack_bins4``: two 4-bit codes per int8 lane, the
reference dense_bin.hpp 4-bit layout) — half the streamed bin bytes;
the kernel unpacks in VMEM against pre-split even/odd weight halves.
Small-B one-hot tiles group MORE features per 128-row MXU tile instead
of padding bins (``_tile_params``).  The leaf-batched DMA kernels tile a
wide feature axis (``_leaves_dma_tiling``) and contract only the feature
steps that hold a real feature: the padding of a ragged last tile is
streamed but never one-hot encoded or multiplied.  Contract: quantized
int32 sums are bit-for-bit identical across every variant; f32 stays
within the hi/lo exactness budget.  ``interpret=None`` interprets on the CPU,
so all of this is testable there, and the entry points batch under
``vmap`` through jax's pallas_call batching rule (the batch axis
becomes a leading grid dimension — what lets multitrain ride these
kernels).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quantize import hist_limbs

__all__ = ["build_histogram_pallas", "build_histogram_pallas_leaves",
           "build_histogram_pallas_leaves_q8", "pack_weights8",
           "wave_trial_channels_pallas", "wave_row_update_pallas",
           "bin_rows_view", "gather_bin_rows", "dense_pass_counts",
           "DEFAULT_ROW_BLOCK", "pad_rows", "LEAF_CHANNELS",
           "Q_LEAF_CHANNELS", "resolve_pipeline",
           "resolve_interpret", "pack_bins4", "unpack_bins4",
           "PACK4_MAX_BINS", "traced_kernels"]

DEFAULT_ROW_BLOCK = 4096
_C = 8  # weight channels (5 used), padded to a power of two for clean tiles
_CB = 5  # channels per leaf block in the leaf-batched kernel (no padding)
LEAF_CHANNELS = 128 // _CB  # 25 leaves per pass (25*5 = 125 <= 128 lanes)
_QCB = 3  # quantized channels per leaf: g_q, h_q, count
Q_LEAF_CHANNELS = 128 // _QCB  # 42 leaves per pass (42*3 = 126 <= 128)

# 4-bit bin packing (reference src/io/dense_bin.hpp IS_4BIT specialization):
# two bin codes per int8 lane, applicable when every bin fits a nibble
PACK4_MAX_BINS = 16

# Kernel pipeline: "dma" streams row blocks of bins + packed weights
# HBM->VMEM through explicitly double-buffered async copies that overlap
# the MXU one-hot contraction; "blockspec" is the original implicit
# per-grid-step operand fetch.  Default: dma ON TPU (where the overlap
# is real); on the CPU the kernels run the interpreter, where the DMA
# machinery is pure emulation overhead, so unresolved calls default to
# the cheaper-to-emulate blockspec form — explicit pipeline="dma"
# forces the DMA form anywhere (the parity tests do).


def resolve_pipeline(pipeline=None) -> str:
    if not pipeline:
        from ..utils.backend import default_backend
        pipeline = "dma" if default_backend() == "tpu" else "blockspec"
    if pipeline not in ("dma", "blockspec"):
        raise ValueError("pallas pipeline must be dma|blockspec, got "
                         f"{pipeline!r}")
    return pipeline


def resolve_interpret(interpret=None) -> bool:
    """None -> interpret exactly when the platform IS the cpu (the test
    suite; Mosaic lowers nowhere else).  On a TPU the kernels always go
    through Mosaic: a kernel it refuses fails the compile of the program
    that holds it, and the boosting loop re-raises with the kernels'
    names and shapes (:func:`traced_kernels`)."""
    if interpret is not None:
        return bool(interpret)
    from ..utils.backend import default_backend
    return default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_rows(n: int, row_block: int = DEFAULT_ROW_BLOCK) -> int:
    """Rows the caller must pad to for the pallas path."""
    return _round_up(max(n, row_block), row_block)


def _check_rows(n: int, row_block: int, kernel: str) -> None:
    if n % row_block != 0 or n == 0:
        raise ValueError(
            f"{kernel} requires the row count to be a non-zero multiple of "
            f"row_block={row_block}, got N={n}; pad inputs to pad_rows(N) "
            f"== {pad_rows(max(n, 1), row_block)} first (masked/padded rows "
            "carry weight 0 and contribute nothing)")


def _check_same_rows(kernel: str, n: int, **named) -> None:
    for name, got in named.items():
        if got != n:
            raise ValueError(
                f"{kernel}: {name} carries {got} rows but the bin matrix "
                f"carries {n}; all row-aligned operands must be padded to "
                "the same pad_rows() length")


@jax.jit
def pack_bins4(bins_t: jnp.ndarray) -> jnp.ndarray:
    """(F, N) uint8 bin codes (all < 16) -> (F, N//2) nibble-packed bytes.

    Row 2j lives in the LOW nibble of byte j, row 2j+1 in the HIGH nibble
    (the reference's 4-bit dense_bin layout along the row axis).  N must
    be even — the pallas row blocks always are."""
    f, n = bins_t.shape
    lo = bins_t[:, 0::2]
    hi = bins_t[:, 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


@jax.jit
def unpack_bins4(packed: jnp.ndarray) -> jnp.ndarray:
    """(..., N//2) packed bytes -> (..., N) interleaved bin codes."""
    lo = packed & jnp.uint8(0xF)
    hi = packed >> 4
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def _tile_params(num_bins: int, f: int, m_cap: int):
    """(padded bin count b, feature group g) for the one-hot contraction.

    The stacked one-hot M dim is g*b; g*b must be a whole number of
    128-row MXU tiles.  Unlike the v1 kernels (which padded b to 64/128),
    b here rounds to a multiple of 8 and small-B shapes fill the tile by
    stacking MORE features per contraction instead of padding bins: at
    B<=16, b=16 with g=8 runs the same 128-row tile with zero padded-bin
    waste (4x fewer MXU flops than b=64).  Per-(feature, bin) sums are
    unchanged — only dead padding moves — so this is bit-compatible."""
    b = max(16, _round_up(num_bins, 8))
    group = 1
    while (group * b) % 128 != 0 and group < 256:
        group *= 2
    while group * 2 <= f and group * 2 * b <= m_cap:
        group *= 2
    if group > f or (group * b) % 128 != 0:
        b = _round_up(num_bins, 128)
        group = 1
    return b, group


_TRACED_KERNELS: list = []


def _kname(kind: str, **dims) -> str:
    """``pallas_call`` name: the kernel and its static shape, so every
    profiler event says which kernel at which size.  Mosaic's own error
    does not carry it, so the names traced in this process are kept for
    the boosting loop to report when a compile is refused
    (:func:`traced_kernels`, models/gbdt.py)."""
    name = "lgbm_" + kind + "".join(f"_{k}{v}" for k, v in dims.items())
    if name not in _TRACED_KERNELS:
        _TRACED_KERNELS.append(name)
    return name


def traced_kernels() -> tuple:
    return tuple(_TRACED_KERNELS)


def _note_kernel(site: str, streamed_bytes: int, features: int = 0,
                 contracted_features: int = 0) -> None:
    """Tally one kernel build (trace-time inside jitted growers; per call
    on eager paths) — exported by TrainRecord like the collective sites.
    The DMA leaf kernels also say how many of their padded feature rows
    they contract (:func:`_leaves_dma_tiling`)."""
    try:
        from ..telemetry.train_record import note_hist_kernel
        note_hist_kernel(site, streamed_bytes, features,
                         contracted_features)
    except Exception:
        pass


def _split_hi_lo(v: jnp.ndarray):
    """Split f32 v into bf16 (hi, lo) with v ≈ hi + lo to ~2^-17 rel.

    hi is v with the low 16 mantissa bits masked off — explicitly via
    integer ops, because XLA's simplifier folds a bf16 round-trip
    (``v - f32(bf16(v))``) into zero under jit.  The masked hi is exactly
    representable in bf16 and ``v - hi`` is exact in f32.
    """
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    hi32 = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)
    return hi32.astype(jnp.bfloat16), (v - hi32).astype(jnp.bfloat16)


def _hist_kernel(bins_ref, w_ref, out_ref, *, num_features: int,
                 num_bins: int, group: int, fstep: int):
    """Accumulate (F*B, C) histograms over one row block.

    ``group`` features share one MXU contraction: their one-hot tiles are
    stacked along M with per-feature bin offsets, so the dot is
    (group*B, R) @ (R, C) — fewer, larger matmuls pipeline better than
    per-feature ones.  The grid is (feature tiles, row blocks) with the row
    dimension innermost, so each feature tile's accumulator stays resident
    in VMEM across the row sweep (bounds VMEM for wide datasets)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...]  # (R, C) bf16
    r = w.shape[0]
    b = num_bins
    iota_gb = jax.lax.broadcasted_iota(jnp.int32, (group * b, r), 0) % b

    # fori_loop (not Python unrolling) keeps one set of intermediates live
    # in VMEM regardless of the tile's feature count.  Each iteration loads
    # an ALIGNED ``fstep``-feature block (Mosaic requires provably-aligned
    # dynamic slice starts) and sweeps it in static ``group``-sized slices;
    # num_features is a multiple of ``fstep`` by construction (padded).
    def do(i, carry):
        f0 = i * fstep
        cols_blk = bins_ref[pl.ds(f0, fstep), :].astype(jnp.int32)
        for k in range(fstep // group):
            cols = cols_blk[k * group:(k + 1) * group]           # (g, R)
            colrep = jnp.repeat(cols, b, axis=0)                 # (g*B, R)
            onehot = (colrep == iota_gb).astype(jnp.bfloat16)
            part = jax.lax.dot_general(
                onehot, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # (g*B, C)
            out_ref[pl.ds((f0 + k * group) * b, group * b)] += part
        return carry

    jax.lax.fori_loop(0, num_features // fstep, do, 0)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_block", "interpret",
                                    "kr"))
def _build_histogram_pallas_bs(bins_t: jnp.ndarray, grad: jnp.ndarray,
                               hess: jnp.ndarray, mask: jnp.ndarray, *,
                               num_bins: int,
                               row_block: int = DEFAULT_ROW_BLOCK,
                               interpret: bool = False,
                               kr: int = 0) -> jnp.ndarray:
    """Implicit-pipeline (BlockSpec-fetched) form of the single-leaf
    histogram kernel — the v1 layout, kept for A/B re-probing."""
    f, n = bins_t.shape
    # Pad bins to a multiple of 64 and pack `group` features per contraction
    # so the stacked one-hot M dim (group*b) fills whole 128-row MXU tiles:
    # at max_bin=63 (the reference's accelerator-recommended setting,
    # docs/GPU-Performance.rst) this doubles throughput vs padding to 128.
    b = _round_up(num_bins, 64)
    group = next((g for g in (2, 4, 8) if (g * b) % 128 == 0), 1)
    while group * 2 <= f and group * 2 * b <= 512:
        group *= 2  # bigger stacked matmuls pipeline better, bounded by VMEM
    if group > f or (group * b) % 128 != 0:
        b = _round_up(num_bins, 128)
        group = 1

    gm = grad * mask
    hm = hess * mask
    g_hi, g_lo = _split_hi_lo(gm)
    h_hi, h_lo = _split_hi_lo(hm)
    z = jnp.zeros_like(g_hi)
    w8 = jnp.stack([g_hi, g_lo, h_hi, h_lo, mask.astype(jnp.bfloat16),
                    z, z, z], axis=-1)  # (N, C) — one fused interleave

    # Feature tiling keeps the VMEM-resident accumulator block bounded no
    # matter how wide the dataset is (wide-sparse/EFB datasets sweep
    # multiple feature tiles over the same rows).  Empirical Mosaic limit:
    # output blocks beyond 8192 sublanes fail scoped-vmem allocation, so
    # cap ft*b at 8192.  The kernel's internal row block is 1024 — measured
    # ~1.8x faster than 4096 at Higgs scale (10.5M x 28, B=256) — while the
    # caller-facing padding contract stays ``row_block``.
    fstep = max(group, 8)  # group is a power of two -> lcm(group, 8)
    ft_cap = max(fstep, 8192 // b // fstep * fstep)
    ft = min(_round_up(f, fstep), ft_cap)
    f_pad = _round_up(f, ft)  # also a multiple of ``fstep`` and ``group``
    if f_pad != f:
        bins_t = jnp.pad(bins_t, ((0, f_pad - f), (0, 0)))
    # narrow inputs (the 1-feature leaf-refit pass) want larger row blocks:
    # per-grid-step overhead dominates their tiny per-block compute
    kr = kr or math.gcd(row_block, 1024)

    grid = (f_pad // ft, n // kr)  # row dim innermost
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_features=ft, num_bins=b,
                          group=group, fstep=fstep),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ft, kr), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kr, _C), lambda i, j: (j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ft * b, _C), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f_pad * b, _C), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * f_pad * b * n * _C,
            bytes_accessed=f_pad * n + n * _C * 2 + f_pad * b * _C * 4,
            transcendentals=0),
        interpret=interpret,
        name=_kname("hist_single_blockspec", f=f_pad, b=b, g=group, kr=kr,
                    n=n),
    )(bins_t, w8)

    out = out.reshape(f_pad, b, _C)
    hist = jnp.stack([out[:, :, 0] + out[:, :, 1],
                      out[:, :, 2] + out[:, :, 3],
                      out[:, :, 4]], axis=-1)
    return hist[:f, :num_bins, :]


def _hist_kernel_dma(bins_hbm, w_hbm, out_ref, *, num_features: int,
                     num_bins: int, group: int, fstep: int, kr: int,
                     nsteps: int, packed: bool):
    """DMA-pipelined form: bins and weight row blocks stream HBM->VMEM
    through two explicitly double-buffered async copies; the copy of
    chunk j+1 is in flight while chunk j feeds the MXU contraction.  The
    whole row sweep lives inside ONE grid step per feature tile, so the
    f32 accumulator block is VMEM-resident start to finish.

    ``packed`` consumes nibble-packed bins (two rows per byte): the
    chunk unpacks in VMEM and contracts each nibble half against its
    half of the pre-split weights — half the streamed bin bytes for the
    same per-(feature, bin) sums."""
    out_ref[...] = jnp.zeros_like(out_ref)
    ft = num_features
    b = num_bins
    f0 = pl.program_id(0) * ft
    kb = kr // 2 if packed else kr            # bin BYTES per chunk lane
    iota_gb = jax.lax.broadcasted_iota(jnp.int32, (group * b, kb), 0) % b

    def body(bbuf, wbuf, bsem, wsem):
        def bins_dma(slot, j):
            return pltpu.make_async_copy(
                bins_hbm.at[pl.ds(f0, ft), pl.ds(j * kb, kb)],
                bbuf.at[slot], bsem.at[slot])

        def w_dma(slot, j):
            if packed:
                return pltpu.make_async_copy(
                    w_hbm.at[:, :, pl.ds(j * kb, kb)], wbuf.at[slot],
                    wsem.at[slot])
            return pltpu.make_async_copy(
                w_hbm.at[:, pl.ds(j * kr, kr)], wbuf.at[slot],
                wsem.at[slot])

        bins_dma(0, 0).start()
        w_dma(0, 0).start()

        def step(j, carry):
            slot = j % 2

            @pl.when(j + 1 < nsteps)
            def _():
                bins_dma((j + 1) % 2, j + 1).start()
                w_dma((j + 1) % 2, j + 1).start()

            bins_dma(slot, j).wait()
            w_dma(slot, j).wait()
            if packed:
                w_halves = (wbuf[slot, 0], wbuf[slot, 1])   # (C, kb) each
            else:
                w_halves = (wbuf[slot],)                    # (C, kr)

            def do(i, c):
                fi = pl.multiple_of(i * fstep, fstep)
                cols_blk = bbuf[slot, pl.ds(fi, fstep), :].astype(jnp.int32)
                nibs = (cols_blk & 0xF, cols_blk >> 4) if packed \
                    else (cols_blk,)
                for k in range(fstep // group):
                    part = None
                    for nib, wh in zip(nibs, w_halves):
                        cols = nib[k * group:(k + 1) * group]    # (g, kb)
                        colrep = jnp.repeat(cols, b, axis=0)     # (g*B, kb)
                        onehot = (colrep == iota_gb).astype(jnp.bfloat16)
                        p = jax.lax.dot_general(
                            onehot, wh, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (g*B, C)
                        part = p if part is None else part + p
                    out_ref[pl.ds((fi + k * group) * b, group * b)] += part
                return c

            jax.lax.fori_loop(0, num_features // fstep, do, 0)
            return carry

        jax.lax.fori_loop(0, nsteps, step, 0)

    wshape = (2, 2, _C, kb) if packed else (2, _C, kr)
    pl.run_scoped(body,
                  pltpu.VMEM((2, ft, kb), bins_hbm.dtype),
                  pltpu.VMEM(wshape, jnp.bfloat16),
                  pltpu.SemaphoreType.DMA((2,)),
                  pltpu.SemaphoreType.DMA((2,)))


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_block", "interpret",
                                    "kr", "packed"))
def _build_histogram_pallas_dma(bins_t: jnp.ndarray, grad: jnp.ndarray,
                                hess: jnp.ndarray, mask: jnp.ndarray, *,
                                num_bins: int, row_block: int,
                                interpret: bool, kr: int,
                                packed: bool) -> jnp.ndarray:
    f = bins_t.shape[0]
    n = bins_t.shape[1] * (2 if packed else 1)
    b, group = _tile_params(num_bins, f, 512)

    gm = grad * mask
    hm = hess * mask
    g_hi, g_lo = _split_hi_lo(gm)
    h_hi, h_lo = _split_hi_lo(hm)
    z = jnp.zeros_like(g_hi)
    # FEATURE-MAJOR (C, N) weights, like the leaf kernels: Mosaic refuses
    # to DMA an 8-lane slab out of a row-major (N, C) array ("slice shape
    # must be aligned to tiling (128)"), a (C, kr) slab is lane-dense
    w8 = jnp.stack([g_hi, g_lo, h_hi, h_lo, mask.astype(jnp.bfloat16),
                    z, z, z], axis=0)                      # (C, N)
    if packed:
        # pre-split weight halves pair each nibble with its own rows, so
        # the kernel never lane-interleaves (Mosaic-unfriendly): half 0
        # carries even rows (low nibbles), half 1 odd rows (high nibbles)
        w8 = jnp.stack([w8[:, 0::2], w8[:, 1::2]])         # (2, C, N/2)

    fstep = max(group, 8)
    ft_cap = max(fstep, 8192 // b // fstep * fstep)
    ft = min(_round_up(f, fstep), ft_cap)
    f_pad = _round_up(f, ft)
    if f_pad != f:
        bins_t = jnp.pad(bins_t, ((0, f_pad - f), (0, 0)))
    kr = kr or math.gcd(row_block, 1024)

    out = pl.pallas_call(
        functools.partial(_hist_kernel_dma, num_features=ft, num_bins=b,
                          group=group, fstep=fstep, kr=kr, nsteps=n // kr,
                          packed=packed),
        grid=(f_pad // ft,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ft * b, _C), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f_pad * b, _C), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * f_pad * b * n * _C,
            bytes_accessed=f_pad * (n // 2 if packed else n) +
            n * _C * 2 + f_pad * b * _C * 4,
            transcendentals=0),
        interpret=interpret,
        name=_kname("hist_single_dma" + ("_packed4" if packed else ""),
                    f=f_pad, b=b, g=group, kr=kr, n=n),
    )(bins_t, w8)

    out = out.reshape(f_pad, b, _C)
    hist = jnp.stack([out[:, :, 0] + out[:, :, 1],
                      out[:, :, 2] + out[:, :, 3],
                      out[:, :, 4]], axis=-1)
    return hist[:f, :num_bins, :]


def build_histogram_pallas(bins_t: jnp.ndarray, grad: jnp.ndarray,
                           hess: jnp.ndarray, mask: jnp.ndarray, *,
                           num_bins: int,
                           row_block: int = DEFAULT_ROW_BLOCK,
                           interpret: bool = None,
                           kr: int = 0, pipeline: str = None,
                           bins_packed: bool = False) -> jnp.ndarray:
    """(F, B, 3) histogram over masked rows from feature-major bin codes.

    Args:
      bins_t: (F, N) integer bin codes — or, with ``bins_packed``, the
        (F, N//2) nibble-packed bytes from :func:`pack_bins4`.  N must be
        a multiple of ``row_block`` (use :func:`pad_rows`).
      grad, hess, mask: (N,) f32; mask is 0.0 for out-of-leaf / padded
        rows.
      num_bins: static global bin count B (padded to a lane-friendly size
        internally; trailing bins stay zero).
      interpret: None = auto (interpret off TPU).
      pipeline: "dma" (explicit double-buffered HBM->VMEM streaming,
        default) or "blockspec" (v1 implicit fetch); None = module
        default.
      bins_packed: bins_t holds two 4-bit codes per byte (requires
        ``num_bins <= PACK4_MAX_BINS``; DMA pipeline only).
    """
    f, np_ = bins_t.shape
    n = np_ * 2 if bins_packed else np_
    _check_rows(n, row_block, "build_histogram_pallas")
    _check_same_rows("build_histogram_pallas", n, grad=grad.shape[0],
                     hess=hess.shape[0], mask=mask.shape[0])
    pipeline = resolve_pipeline(pipeline)
    interpret = resolve_interpret(interpret)
    if bins_packed:
        if num_bins > PACK4_MAX_BINS:
            raise ValueError(f"bins_packed requires num_bins <= "
                             f"{PACK4_MAX_BINS}, got {num_bins}")
        pipeline = "dma"  # the packed layout exists only on the DMA path
    _note_kernel(f"ops/hist_kernel/single/{pipeline}"
                 + ("/packed4" if bins_packed else ""),
                 f * np_ * bins_t.dtype.itemsize + n * _C * 2 +
                 f * num_bins * 3 * 4)
    if pipeline == "dma":
        return _build_histogram_pallas_dma(
            bins_t, grad, hess, mask, num_bins=num_bins,
            row_block=row_block, interpret=interpret, kr=kr,
            packed=bins_packed)
    return _build_histogram_pallas_bs(
        bins_t, grad, hess, mask, num_bins=num_bins, row_block=row_block,
        interpret=interpret, kr=kr)


# ---------------------------------------------------------------------------
# Leaf-channel batched kernel: 25 leaf histograms per pass.
#
# The single-leaf kernel above uses only 5 of the MXU's 128 output lanes
# (the one-hot contraction's N dimension); the systolic array computes the
# other 123 for free.  This variant packs LEAF_CHANNELS=25 leaves x 5 weight
# channels (g_hi, g_lo, h_hi, h_lo, count — nothing wasted) into the lane
# dimension: each row carries a leaf-channel id ``ch`` in [0, 25) (or -1 =
# inactive), the kernel expands the row's weight vector into the 5 lanes of
# its leaf's lane-block, and ONE contraction per row block accumulates all
# 25 histograms.  A tree grower that batches up to 25 splits per wave
# (learner/wave.py) gets its smaller-child histograms for the price of one
# full pass — which removes the need to physically partition rows at all
# (PERF.md round-3 analysis: row movement was 55-60%% of tree time).
# ---------------------------------------------------------------------------


@jax.jit
def pack_weights8(grad: jnp.ndarray, hess: jnp.ndarray,
                  mask: jnp.ndarray) -> jnp.ndarray:
    """(8, N) bf16 FEATURE-MAJOR weight rows [g_hi, g_lo, h_hi, h_lo,
    count, 0, 0, 0].

    Precompute once per tree: gradients do not change across waves, only
    the per-row leaf channel does.  ``mask`` may carry bagging weights
    (GOSS amplification) — they scale grad/hess, while the count channel
    is strictly 0/1 row membership (reference counts rows, not weights).
    """
    gm = grad * mask
    hm = hess * mask
    g_hi, g_lo = _split_hi_lo(gm)
    h_hi, h_lo = _split_hi_lo(hm)
    z = jnp.zeros_like(g_hi)
    return jnp.stack([g_hi, g_lo, h_hi, h_lo,
                      (mask > 0).astype(jnp.bfloat16), z, z, z], axis=0)


def _hist_leaves_kernel(bins_ref, w_ref, ch_ref, out_ref, *,
                        num_features: int, num_bins: int, group: int,
                        fstep: int):
    """Accumulate (F*B, 128) lane-packed leaf histograms over one row
    block (25 leaves x 5 channels in the 128-lane dimension).

    Same feature-major rhs-transposed form as the q8 kernel (the dot
    contracts dim 1 of BOTH operands) — measured 120 ms vs 165 ms for
    the row-major lhs-major form at 10.5M x 28 x 256."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = w_ref[...]                      # (8, R) bf16 feature-major
    ch = ch_ref[...]                    # (1, R) int32
    r = w.shape[1]
    b = num_bins

    # Expand (8, R) weights into (128, R): sublane l carries weight
    # channel l%_CB iff the row's leaf channel == l//_CB.  All arithmetic
    # — Mosaic cannot relayout i1 masks between replicated operand
    # orientations, so the equality select is ``relu(1 - |ch - leaf|)``
    # (exactly 1.0 on match for integer distances); channel tiling is a
    # sublane concatenate sliced to 128 (the last 3 sublanes select leaf
    # 25 which no row carries -> zero).  Pure VPU work, no gather.
    subl = jax.lax.broadcasted_iota(jnp.int32, (128, r), 0)
    leaf_of_subl = subl // _CB
    d = (ch - leaf_of_subl).astype(jnp.float32)     # (128, R) broadcast
    sel = jnp.maximum(0.0, 1.0 - jnp.abs(d)).astype(jnp.bfloat16)
    w5 = w[:_CB, :]
    wtile = jnp.concatenate([w5] * (128 // _CB + 1), axis=0)[:128]
    w128t = wtile * sel                              # (128, R)

    iota_gb = jax.lax.broadcasted_iota(jnp.int32, (group * b, r), 0) % b

    def do(i, carry):
        f0 = i * fstep
        cols_blk = bins_ref[pl.ds(f0, fstep), :].astype(jnp.int32)
        for k in range(fstep // group):
            cols = cols_blk[k * group:(k + 1) * group]           # (g, R)
            colrep = jnp.repeat(cols, b, axis=0)                 # (g*B, R)
            onehot = (colrep == iota_gb).astype(jnp.bfloat16)
            part = jax.lax.dot_general(
                onehot, w128t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)              # (g*B, 128)
            out_ref[pl.ds((f0 + k * group) * b, group * b)] += part
        return carry

    jax.lax.fori_loop(0, num_features // fstep, do, 0)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_block", "interpret"))
def _build_histogram_pallas_leaves_bs(bins_t: jnp.ndarray, w8: jnp.ndarray,
                                      ch: jnp.ndarray, *, num_bins: int,
                                      row_block: int = DEFAULT_ROW_BLOCK,
                                      interpret: bool = False
                                      ) -> jnp.ndarray:
    """Implicit-pipeline (BlockSpec-fetched) 25-leaf kernel (v1 layout)."""
    f, n = bins_t.shape
    b = _round_up(num_bins, 64)
    group = next((g for g in (2, 4, 8) if (g * b) % 128 == 0), 1)
    while group * 2 <= f and group * 2 * b <= 1024:
        group *= 2
    if group > f or (group * b) % 128 != 0:
        b = _round_up(num_bins, 128)
        group = 1

    ch2 = ch.astype(jnp.int32).reshape(1, n)               # (1, N)

    # The (ft*b, 128) f32 accumulator must stay well inside VMEM next to
    # the bins / weight blocks (cap 8192 sublanes); kr=4096 + M<=1024
    # measured best for the bf16 form at Higgs scale (proto_bf16_fm.py:
    # 120 ms vs 165 ms for the old row-major kr=1024 layout).
    fstep = max(group, 8)
    ft_cap = max(fstep, 8192 // b // fstep * fstep)
    ft = min(_round_up(f, fstep), ft_cap)
    f_pad = _round_up(f, ft)
    if f_pad != f:
        bins_t = jnp.pad(bins_t, ((0, f_pad - f), (0, 0)))
    kr = math.gcd(row_block, 4096)

    grid = (f_pad // ft, n // kr)
    out = pl.pallas_call(
        functools.partial(_hist_leaves_kernel, num_features=ft, num_bins=b,
                          group=group, fstep=fstep),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ft, kr), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_C, kr), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kr), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ft * b, 128), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f_pad * b, 128), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * f_pad * b * n * 128,
            bytes_accessed=f_pad * n + n * (_C * 2 + 4) + f_pad * b * 512,
            transcendentals=0),
        interpret=interpret,
        name=_kname("hist_leaves_blockspec", f=f_pad, b=b, g=group, kr=kr,
                    n=n),
    )(bins_t, w8, ch2)

    out = out[:, :LEAF_CHANNELS * _CB].reshape(f_pad, b, LEAF_CHANNELS, _CB)
    hist = jnp.stack([out[..., 0] + out[..., 1],
                      out[..., 2] + out[..., 3],
                      out[..., 4]], axis=-1)              # (F, B, 25, 3)
    return jnp.transpose(hist, (2, 0, 1, 3))[:, :f, :num_bins, :]


def _leaves_dma_common(*refs, num_features, contracted, num_bins, group,
                       fstep, kr, nsteps, packed, make_w128, onehot_dtype,
                       acc_dtype, segment=False):
    """Shared DMA pipeline of the two leaf-batched kernels: bins,
    feature-major weights and the leaf-channel row stream HBM->VMEM via
    double-buffered async copies overlapping the contraction.
    ``make_w128(w_chunk, ch_chunk)`` expands the (8, r) weights into the
    lane-packed (128, r) right operand (bf16 hi/lo or int8 form).

    Only the first ``contracted`` feature rows (the real features rounded
    up to ``fstep``) are one-hot encoded and contracted: a ragged last
    tile runs fewer feature steps, and the accumulator rows it skips
    keep the zeros they start with.

    ``nsteps``, the row blocks swept, is static (every row block of the
    operands: the verify, root and subsample passes, where every row is
    in a channel) or, when None, read from a prefetched scalar: a pass
    over rows compacted by :func:`_compact_rows_dma` loops over the
    blocks that hold active lanes and no further.  The operands keep
    their static shapes; only the trip count depends on the data.
    ``segment`` (with ``nsteps`` None): the prefetched scalars are (trip
    count, first block), for a pass that is accumulated in several calls
    (ops/quantize.py: int32 sums that could wrap over the whole pass)."""
    if nsteps is None:
        steps_ref, bins_hbm, w_hbm, ch_hbm, out_ref = refs
        nsteps = steps_ref[0]
    else:
        bins_hbm, w_hbm, ch_hbm, out_ref = refs

    def blk(j):
        """Row block ``j`` of this call in the operands: a call that
        accumulates one segment of a pass (``segment``) starts at the
        block its second prefetched scalar names."""
        return j + steps_ref[1] if segment else j

    out_ref[...] = jnp.zeros_like(out_ref)
    ft = num_features
    b = num_bins
    f0 = pl.program_id(0) * ft
    if contracted % ft == 0:
        fsteps = ft // fstep                  # every tile is full: static
    else:
        fsteps = jnp.minimum(ft, contracted - f0) // fstep
    kb = kr // 2 if packed else kr
    iota_gb = jax.lax.broadcasted_iota(jnp.int32, (group * b, kb), 0) % b

    def body(bbuf, wbuf, cbuf, bsem, wsem, csem):
        def bins_dma(slot, j):
            return pltpu.make_async_copy(
                bins_hbm.at[pl.ds(f0, ft), pl.ds(blk(j) * kb, kb)],
                bbuf.at[slot], bsem.at[slot])

        def w_dma(slot, j):
            if packed:
                return pltpu.make_async_copy(
                    w_hbm.at[:, :, pl.ds(blk(j) * kb, kb)], wbuf.at[slot],
                    wsem.at[slot])
            return pltpu.make_async_copy(
                w_hbm.at[:, pl.ds(blk(j) * kr, kr)], wbuf.at[slot],
                wsem.at[slot])

        def ch_dma(slot, j):
            if packed:
                return pltpu.make_async_copy(
                    ch_hbm.at[:, :, pl.ds(blk(j) * kb, kb)], cbuf.at[slot],
                    csem.at[slot])
            return pltpu.make_async_copy(
                ch_hbm.at[:, pl.ds(blk(j) * kr, kr)], cbuf.at[slot],
                csem.at[slot])

        def start(slot, j):
            bins_dma(slot, j).start()
            w_dma(slot, j).start()
            ch_dma(slot, j).start()

        if segment:
            # a segment past the pass's last block runs no step: a copy
            # started here would never be waited for
            pl.when(nsteps > 0)(lambda: start(0, 0))
        else:
            start(0, 0)

        def step(j, carry):
            slot = j % 2

            @pl.when(j + 1 < nsteps)
            def _():
                start((j + 1) % 2, j + 1)

            bins_dma(slot, j).wait()
            w_dma(slot, j).wait()
            ch_dma(slot, j).wait()
            if packed:
                w128s = (make_w128(wbuf[slot, 0], cbuf[slot, 0]),
                         make_w128(wbuf[slot, 1], cbuf[slot, 1]))
            else:
                w128s = (make_w128(wbuf[slot], cbuf[slot]),)

            def do(i, c):
                fi = pl.multiple_of(i * fstep, fstep)
                cols_blk = bbuf[slot, pl.ds(fi, fstep), :].astype(jnp.int32)
                nibs = (cols_blk & 0xF, cols_blk >> 4) if packed \
                    else (cols_blk,)
                for k in range(fstep // group):
                    part = None
                    for nib, w128t in zip(nibs, w128s):
                        cols = nib[k * group:(k + 1) * group]
                        colrep = jnp.repeat(cols, b, axis=0)
                        onehot = (colrep == iota_gb).astype(onehot_dtype)
                        p = jax.lax.dot_general(
                            onehot, w128t, (((1,), (1,)), ((), ())),
                            preferred_element_type=acc_dtype)  # (g*B, 128)
                        part = p if part is None else part + p
                    out_ref[pl.ds((fi + k * group) * b, group * b)] += part
                return c

            jax.lax.fori_loop(0, fsteps, do, 0)
            return carry

        jax.lax.fori_loop(0, nsteps, step, 0)

    if packed:
        wshape, cshape = (2, 2, _C, kb), (2, 2, 1, kb)
    else:
        wshape, cshape = (2, _C, kr), (2, 1, kr)
    pl.run_scoped(body,
                  pltpu.VMEM((2, ft, kb), bins_hbm.dtype),
                  pltpu.VMEM(wshape, w_hbm.dtype),
                  pltpu.VMEM(cshape, ch_hbm.dtype),
                  pltpu.SemaphoreType.DMA((2,)),
                  pltpu.SemaphoreType.DMA((2,)),
                  pltpu.SemaphoreType.DMA((2,)))


def _make_w128_bf16(w, ch):
    """(8, r) bf16 weights + (1, r) i32 channels -> (128, r) lane-packed
    right operand (same arithmetic as _hist_leaves_kernel)."""
    r = w.shape[1]
    subl = jax.lax.broadcasted_iota(jnp.int32, (128, r), 0)
    d = (ch.astype(jnp.int32) - subl // _CB).astype(jnp.float32)
    sel = jnp.maximum(0.0, 1.0 - jnp.abs(d)).astype(jnp.bfloat16)
    wtile = jnp.concatenate([w[:_CB]] * (128 // _CB + 1), axis=0)[:128]
    return wtile * sel


def _make_w128_q8(w, ch):
    """(8, r) i8 weights + (1, r) i8 channels -> (128, r) int8 operand
    (same arithmetic as _hist_leaves_q8_kernel: 32-bit build, i8 pack)."""
    r = w.shape[1]
    subl = jax.lax.broadcasted_iota(jnp.int32, (128, r), 0)
    sel = (ch.astype(jnp.int32) == subl // _QCB).astype(jnp.int32)
    w3 = w[:_QCB].astype(jnp.int32)
    wtile = jnp.concatenate([w3] * (128 // _QCB + 1), axis=0)[:128]
    return (wtile * sel).astype(jnp.int8)


# ---------------------------------------------------------------------------
# Row compaction in front of the DMA leaf kernels.
#
# After a tree's first pass, the channel row ``ch`` of a histogram pass
# is mostly -1: the wave and endgame passes build the SMALLER children
# only (learner/wave.py), 8-37%% of the rows; in a tree of a booster
# that samples rows every pass, the first included, also leaves out the
# rows that are not in the bag (0.3 of 8-37%% under GOSS's defaults, 0.3
# of all rows in the first pass).  A row with ch == -1 gets
# an all-zero column of the (128, kr) right operand and the MXU does its
# fc x B x 128 MACs on it all the same.  :func:`_compact_rows_dma` moves
# the lanes with ch >= 0 of every operand the leaf kernel streams (bins,
# weights, ch) to the front, in their original order, block by block;
# the leaf kernel then loops over the row blocks that hold active lanes.
#
# How: rows are streamed in blocks of ``kb`` lanes, each handled in
# sub-blocks of ``_CP_SUB`` lanes.  A sub-block's active lanes go to a
# 128-aligned WINDOW of ``_CP_SUB + 128`` lanes of the block's output
# buffer by one 0/1 selection matmul on the MXU: ``(rows, sub) .
# (window, sub)^T`` with ``sel[p, s] = (dest[s] == p)``.  Bin codes,
# int8 levels, bf16 halves and channel ids are all exact in bf16, every
# output lane has at most one non-zero product, and the accumulator is
# f32, so the moved values are exact.  The window's first tile carries
# the partial tile the previous sub-block left (a loop-carried value).
# ``dest`` (each lane's place in its window, -1 for inactive lanes), the
# windows' tile offsets and the blocks' output offsets are small XLA
# work on ``ch`` (:func:`_compact_plan`) but the in-sub-block prefix
# count, one triangular matmul in a kernel of its own.  Blocks
# are written to HBM end to end at 128-aligned offsets as FIXED-size
# (kb-lane) copies: a block's tail beyond its active lanes is
# overwritten by the next block's copy (each copy is waited for before
# the next starts), the last by one block of padding.  Padding lanes
# carry ch = -1 and zero weights.  No gather, no sort, no permutation
# kept from pass to pass.
# ---------------------------------------------------------------------------

_CP_SUB = 512     # source lanes per selection matmul
_CP_KB = 8192     # lanes per compaction block, at most (a power of two)
_CP_CH_BITS = 6   # ch + 1 (0..42) rides in the low bits of ``code``
_CP_GROUP = 96    # bin rows per selection matmul, at most (whole u8 tiles)
_CP_VMEM = 6 << 20  # the bin buffers' budget (in + out, double-buffered)


_CP_PLAN_ROWS = 1024   # sub-blocks per step of the plan kernel


def _compact_code_kernel(ch_ref, rem_ref, code_ref):
    """``code`` of ``_CP_PLAN_ROWS`` sub-blocks, one a row: the exclusive
    prefix count of each row's active lanes is one triangular matmul.
    (In XLA the same matmul costs the grower 45 s of compile time and a
    cumulative sum 1.6 ms a pass more at 21M rows: PERF.md section 6.)"""
    sub = ch_ref.shape[1]
    ch = ch_ref[...]
    act = ch >= 0
    tri = (jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0) <
           jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1))
    pre = jnp.dot(act.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    code_ref[...] = jnp.where(
        act, ((pre + rem_ref[...]) << _CP_CH_BITS) | (ch + 1), -1)


def _compact_plan(ch, *, kb: int, kr: int, interpret: bool):
    """What the compaction kernel is told about ``ch`` (N,) int32:

    ``code`` (1, N) int32: ``dest << 6 | (ch + 1)`` for an active lane,
      ``dest`` its lane in its sub-block's window; -1 for the others.
    ``wt`` (N / sub + 1,) int32: each sub-block's window start in its
      block's output buffer, in 128-lane tiles.
    ``off`` (N / kb + 1,) int32: each block's offset in the compacted
      arrays, in lanes (a multiple of 128); the last entry is the total.
    ``steps`` (1,) int32: the ``kr``-lane blocks that hold the total (at
      least one: the leaf kernel's pipeline always fetches block 0).
    ``counts`` (3,) int32: the active lanes, the ``kb``-lane blocks and
      how many of those hold an active lane (the pass log's
      ``active_rows``, ``blocks``, ``blocks_active``: learner/wave.py).

    The counts and offsets are XLA work on N / sub integers; ``code``,
    which needs each lane's place among its sub-block's active lanes, is
    a kernel of its own (``lgbm_hist_compact_plan_...``).
    """
    n = ch.shape[0]
    sub = _CP_SUB
    nsub = kb // sub
    ch2 = ch.reshape(n // sub, sub)
    cnt = jnp.sum(ch2 >= 0, axis=1, dtype=jnp.int32).reshape(n // kb, nsub)
    start = jnp.cumsum(cnt, axis=1) - cnt          # lane in the block
    rows = min(_CP_PLAN_ROWS, n // sub)
    code = pl.pallas_call(
        _compact_code_kernel,
        grid=(pl.cdiv(n // sub, rows),),
        in_specs=[pl.BlockSpec((rows, sub), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, sub), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // sub, sub), jnp.int32),
        interpret=interpret,
        name=_kname("hist_compact_plan", s=sub, r=rows, n=n),
    )(ch2, (start % 128).reshape(n // sub, 1))
    wt = jnp.concatenate([(start // 128).reshape(-1),
                          jnp.zeros((1,), jnp.int32)])
    held = jnp.sum(cnt, axis=1)                    # active lanes a block
    padded = _round_up(held, 128)
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(padded)])
    steps = jnp.maximum(1, -(-off[-1:] // kr))
    # The log's counts read the blocks' totals as a value of their own:
    # without the barrier XLA folds each of the two sums back into a
    # reduction over all of ``ch``, 0.25 ms each a pass at 45.8M rows
    # (PERF.md section 6, PR 36).
    held = jax.lax.optimization_barrier(held)
    counts = jnp.stack([jnp.sum(held), jnp.int32(n // kb),
                        jnp.sum(held > 0, dtype=jnp.int32)])
    return code.reshape(1, n), wt, off, steps, counts


def _compact_kernel(wt_ref, off_ref, bins_hbm, w_hbm, code_hbm, bins_out,
                    w_out, ch_out, *, kb: int, nblk: int, fc: int,
                    npad: int):
    """See the section comment.  ``fc``: the bin rows moved (the rows the
    leaf kernel contracts; the operands' other rows are padding)."""
    sub = _CP_SUB
    nsub = kb // sub
    wd = sub + 128
    ntile = wd // 128
    f_pad = bins_hbm.shape[0]
    # matmul row groups: ``_CP_GROUP`` bin rows each, the 8 weight rows
    # (the channel id in the last) behind the last group's
    groups = [(r0, min(r0 + _CP_GROUP, fc)) for r0 in range(0, fc, _CP_GROUP)]
    iota_wd = jax.lax.broadcasted_iota(jnp.int32, (wd, sub), 0)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (8, sub), 0)

    def body(bbuf, wbuf, cbuf, bo, wo, co, isem, osem):
        def in_dmas(slot, j):
            lanes = pl.ds(j * kb, kb)
            return (pltpu.make_async_copy(bins_hbm.at[:, lanes],
                                          bbuf.at[slot], isem.at[0, slot]),
                    pltpu.make_async_copy(w_hbm.at[:, lanes],
                                          wbuf.at[slot], isem.at[1, slot]),
                    pltpu.make_async_copy(code_hbm.at[:, lanes],
                                          cbuf.at[slot], isem.at[2, slot]))

        def out_dmas(slot, at):
            lanes = pl.ds(pl.multiple_of(at, 128), kb)
            return tuple(
                pltpu.make_async_copy(buf.at[slot, :, pl.ds(0, kb)],
                                      out.at[:, lanes], osem.at[i, slot])
                for i, (buf, out) in enumerate(
                    ((bo, bins_out), (wo, w_out), (co, ch_out))))

        def store_bins(slot, r0, r1, lanes, res):
            """Rows [r0, r1) of the bins, and up to the next group's
            start the zeros the leaf kernel streams and never reads."""
            end = min(_round_up(r1, _CP_GROUP), f_pad)
            bins = res.astype(jnp.int32)
            if end > r1:
                bins = jnp.concatenate(
                    [bins, jnp.zeros((end - r1, res.shape[1]), jnp.int32)],
                    axis=0)
            bo[slot, r0:end, lanes] = bins.astype(bo.dtype)

        def store_w(slot, lanes, res):
            """The 8 weight rows in their own dtype; the channel id comes
            out of the last, which goes back to the zero it was."""
            co[slot, :, lanes] = res[7:8].astype(jnp.int32) - 1
            wrow = jax.lax.broadcasted_iota(jnp.int32, res.shape, 0)
            wv = jnp.where(wrow == 7, 0.0, res)
            if jnp.issubdtype(wo.dtype, jnp.integer):
                wv = wv.astype(jnp.int32)
            wo[slot, :, lanes] = wv.astype(wo.dtype)

        for d in in_dmas(0, 0):
            d.start()

        def step(j, carry):
            slot = j % 2

            @pl.when(j + 1 < nblk)
            def _():
                for d in in_dmas((j + 1) % 2, j + 1):
                    d.start()

            for d in in_dmas(slot, j):
                d.wait()

            def move(k, tiles0):
                lanes = pl.ds(pl.multiple_of(k * sub, sub), sub)
                code = cbuf[slot, :, lanes]                    # (1, sub)
                sel = ((code >> _CP_CH_BITS) == iota_wd).astype(jnp.bfloat16)
                chp1 = (code & ((1 << _CP_CH_BITS) - 1)).astype(jnp.float32)
                i = j * nsub + k
                w0 = wt_ref[i]
                full = wt_ref[i + 1] - w0
                window = pl.ds(pl.multiple_of(w0 * 128, 128), wd)
                nxt = []
                for gi, (r0, r1) in enumerate(groups):
                    data = [bbuf[slot, r0:r1, lanes].astype(jnp.int32)
                            .astype(jnp.float32)]
                    last = gi == len(groups) - 1
                    if last:
                        wf = wbuf[slot, :, lanes].astype(jnp.float32)
                        data.append(jnp.where(row8 == 7, chp1, wf))
                    rows = r1 - r0 + (8 if last else 0)
                    if rows % 16:      # whole bf16 tiles for the MXU
                        data.append(jnp.zeros((16 - rows % 16, sub),
                                              jnp.float32))
                    res = jax.lax.dot_general(
                        jnp.concatenate(data, axis=0).astype(jnp.bfloat16),
                        sel, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)[:rows]
                    # the window's first tile: what the sub-block before
                    # left of it, and this one's lanes behind
                    res = jnp.concatenate(
                        [res[:, :128] + tiles0[gi], res[:, 128:]], axis=1)
                    store_bins(slot, r0, r1, window, res[:r1 - r0])
                    if last:
                        store_w(slot, window, res[r1 - r0:])
                    # the partial tile the next window starts with
                    t_ = res[:, (ntile - 1) * 128:]
                    for t in range(ntile - 2, -1, -1):
                        t_ = jnp.where(full == t,
                                       res[:, t * 128:(t + 1) * 128], t_)
                    nxt.append(t_)
                return tuple(nxt)

            jax.lax.fori_loop(
                0, nsub, move,
                tuple(jnp.zeros((r1 - r0 + (8 if gi == len(groups) - 1
                                            else 0), 128), jnp.float32)
                      for gi, (r0, r1) in enumerate(groups)))

            # copies land in order: block j's tail is block j+1's to
            # overwrite, so j-1's copy ends before j's starts
            @pl.when(j >= 1)
            def _():
                for d in out_dmas((j + 1) % 2, off_ref[j - 1]):
                    d.wait()

            for d in out_dmas(slot, off_ref[j]):
                d.start()
            return carry

        jax.lax.fori_loop(0, nblk, step, 0)
        for d in out_dmas((nblk - 1) % 2, off_ref[nblk - 1]):
            d.wait()
        # padding behind the last active lane, as far as the leaf
        # kernel's last row block can reach
        pad = nblk % 2
        for r0, r1 in groups:
            store_bins(pad, r0, r1, pl.ds(0, kb),
                       jnp.zeros((r1 - r0, kb), jnp.float32))
        store_w(pad, pl.ds(0, kb), jnp.zeros((8, kb), jnp.float32))
        for i in range(npad):
            for d in out_dmas(pad, off_ref[nblk] + i * kb):
                d.start()
            for d in out_dmas(pad, off_ref[nblk] + i * kb):
                d.wait()

    pl.run_scoped(body,
                  pltpu.VMEM((2, f_pad, kb), bins_hbm.dtype),
                  pltpu.VMEM((2, 8, kb), w_hbm.dtype),
                  pltpu.VMEM((2, 1, kb), jnp.int32),
                  # a dense block's last window ends a tile past kb
                  pltpu.VMEM((2, f_pad, kb + 128), bins_out.dtype),
                  pltpu.VMEM((2, 8, kb + 128), w_out.dtype),
                  pltpu.VMEM((2, 1, kb + 128), jnp.int32),
                  pltpu.SemaphoreType.DMA((3, 2)),
                  pltpu.SemaphoreType.DMA((3, 2)))


def _compact_block(n: int, f_pad: int) -> int:
    """Lanes per compaction block: the largest power of two up to
    ``_CP_KB`` that divides ``n`` and keeps the bin buffers in budget."""
    kb = math.gcd(n, _CP_KB)
    while kb > _CP_SUB and 4 * f_pad * kb > _CP_VMEM:
        kb //= 2
    if kb % _CP_SUB:
        raise ValueError(f"row compaction needs {_CP_SUB} | N, got N={n}")
    return kb


def _compact_rows_dma(bins_t, w, ch2, *, fc: int, kr: int, interpret: bool):
    """Move the lanes with ``ch2 >= 0`` of ``bins_t`` (f_pad, N) uint8
    (its first ``fc`` rows: the contracted ones), ``w`` (8, N) and ``ch2``
    (1, N) int32 to the front, block by block and in their order.
    Returns ``(bins, w, ch, steps, counts)``: the arrays with some lanes
    more than N, of which the first ``steps[0] * kr`` hold every active
    lane and else padding (ch -1, zero weights), ``steps`` (1,) int32 for
    the leaf kernel's scalar prefetch, and the plan's ``counts``."""
    f_pad, n = bins_t.shape
    kb = _compact_block(n, f_pad)
    npad = -(-kr // kb)
    code, wt, off, steps, counts = _compact_plan(ch2[0], kb=kb, kr=kr,
                                                 interpret=interpret)
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    lanes = n + npad * kb
    outs = pl.pallas_call(
        functools.partial(_compact_kernel, kb=kb, nblk=n // kb, fc=fc,
                          npad=npad),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[any_, any_, any_], out_specs=[any_, any_, any_]),
        out_shape=[jax.ShapeDtypeStruct((f_pad, lanes), bins_t.dtype),
                   jax.ShapeDtypeStruct((8, lanes), w.dtype),
                   jax.ShapeDtypeStruct((1, lanes), jnp.int32)],
        interpret=interpret,
        name=_kname("hist_compact_dma", f=f_pad, fc=fc, s=_CP_SUB, kb=kb,
                    n=n),
    )(wt, off, bins_t, w, code)
    return (*outs, steps, counts)


def dense_pass_counts(n: int, ch=None):
    """``[rows, active_rows, blocks, blocks_active]`` (4,) int32 of a pass
    that loops over all ``n`` lanes: every block counted as one that
    holds work (blocks of the lanes :func:`_compact_block` gives narrow
    bins), the active lanes those with ``ch >= 0`` (all of them where the
    caller knows so and hands no ``ch``).  What a compacted pass gives
    in :func:`_leaves_dma_call`'s ``rows``."""
    blocks = -(-n // (_CP_KB if n % _CP_SUB else math.gcd(n, _CP_KB)))
    active = n if ch is None else jnp.sum(ch >= 0)
    return jnp.stack([jnp.asarray(v, jnp.int32)
                      for v in (n, active, blocks, blocks)])


# stacked one-hot M dim (group * b) cap of the two DMA leaf kernels
_LEAVES_M_CAP = 1024
_LEAVES_Q8_M_CAP = 2048


def _leaves_dma_tiling(f: int, num_bins: int, m_cap: int):
    """Static tiling of the DMA leaf kernels: ``(b, group, fstep, ft,
    f_pad, fc)``.  The feature axis is streamed in ``f_pad // ft`` tiles
    of ``ft`` rows (the (ft*b, 128) accumulator block is capped at 8192
    sublanes, 4 MB), swept ``fstep`` rows a step; ``fc`` = ``f`` rounded
    up to ``fstep`` is how many of the ``f_pad`` rows hold data and are
    contracted (67 features at B=256: 72 of 96)."""
    b, group = _tile_params(num_bins, f, m_cap)
    fstep = max(group, 8)
    ft_cap = max(fstep, 8192 // b // fstep * fstep)
    ft = min(_round_up(f, fstep), ft_cap)
    return b, group, fstep, ft, _round_up(f, ft), _round_up(f, fstep)


def _leaves_dma_call(bins_t, w, ch2, *, kind, num_bins, interpret, packed,
                     m_cap, kr0, make_w128, onehot_dtype, acc_dtype,
                     out_dtype, row_block, compact=False, acc_rows=0):
    """Shared wrapper plumbing of the two DMA leaf-kernel builders.
    Returns ``(out, f_pad, rows)``: ``rows`` is how many rows the kernel
    looped over, the static N, or (``compact``) the device vector
    ``[rows, active_rows, blocks, blocks_active]`` (4,) int32.  With
    ``acc_rows`` the pass is accumulated ``acc_rows`` rows a call and
    ``out`` is the list of the calls' outputs (ops/quantize.py)."""
    f = bins_t.shape[0]
    n = bins_t.shape[1] * (2 if packed else 1)
    b, group, fstep, ft, f_pad, fc = _leaves_dma_tiling(f, num_bins, m_cap)
    if packed:
        w = jnp.stack([w[:, 0::2], w[:, 1::2]])       # (2, 8, N/2)
        ch2 = jnp.stack([ch2[:, 0::2], ch2[:, 1::2]])  # (2, 1, N/2)
    if f_pad != f:
        bins_t = jnp.pad(bins_t, ((0, f_pad - f), (0, 0)))
    kr = math.gcd(row_block, kr0)
    specs = dict(
        grid=(f_pad // ft,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ft * b, 128), lambda i, *_: (i, 0),
                               memory_space=pltpu.VMEM))
    if compact:
        bins_t, w, ch2, steps, counts = _compact_rows_dma(
            bins_t, w, ch2, fc=fc, kr=kr, interpret=interpret)
        n = bins_t.shape[1]
        rows = jnp.concatenate([steps * kr, counts])
    else:
        rows = n

    def call(prefetch, **static):
        if prefetch is None:
            operands, sp = (bins_t, w, ch2), specs
        else:
            operands = (prefetch, bins_t, w, ch2)
            sp = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, **specs))
        return pl.pallas_call(
            functools.partial(_leaves_dma_common, num_features=ft,
                              contracted=fc, num_bins=b, group=group,
                              fstep=fstep, kr=kr,
                              packed=packed, make_w128=make_w128,
                              onehot_dtype=onehot_dtype, acc_dtype=acc_dtype,
                              **static),
            **sp,
            out_shape=jax.ShapeDtypeStruct((f_pad * b, 128), out_dtype),
            cost_estimate=pl.CostEstimate(
                flops=2 * fc * b * n * 128,
                bytes_accessed=f_pad * (n // 2 if packed else n) +
                n * (_C * 2 + 4) + f_pad * b * 512,
                transcendentals=0),
            interpret=interpret,
            name=_kname(kind + "_dma" + ("_packed4" if packed else "")
                        + ("_seg" if static.get("segment") else ""),
                        f=f_pad, fc=fc, b=b, g=group, kr=kr, n=n),
        )(*operands)

    if acc_rows:
        # one call a segment of ``acc_rows`` rows, each told its first
        # block and its trip count: all the blocks of a dense pass, the
        # blocks that hold active lanes of a compacted one
        seg, blocks = max(1, acc_rows // kr), n // kr
        have = steps[0] if compact else jnp.int32(blocks)
        out = [call(jnp.stack([jnp.clip(have - first, 0, seg),
                               jnp.int32(first)]),
                    nsteps=None, segment=True)
               for first in range(0, blocks, seg)]
    elif compact:
        out = call(steps, nsteps=None)
    else:
        out = call(None, nsteps=n // kr)
    return out, f_pad, rows


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_block", "interpret",
                                    "packed", "compact"))
def _build_histogram_pallas_leaves_dma(bins_t, w8, ch, *, num_bins,
                                       row_block, interpret, packed,
                                       compact=False):
    n = w8.shape[1]
    ch2 = ch.astype(jnp.int32).reshape(1, n)
    out, f_pad, rows = _leaves_dma_call(
        bins_t, w8, ch2, kind="hist_leaves", num_bins=num_bins,
        interpret=interpret,
        packed=packed, m_cap=_LEAVES_M_CAP, kr0=4096,
        make_w128=_make_w128_bf16,
        onehot_dtype=jnp.bfloat16, acc_dtype=jnp.float32,
        out_dtype=jnp.float32, row_block=row_block, compact=compact)
    f = bins_t.shape[0]
    b = out.shape[0] // f_pad
    out = out[:, :LEAF_CHANNELS * _CB].reshape(f_pad, b, LEAF_CHANNELS, _CB)
    hist = jnp.stack([out[..., 0] + out[..., 1],
                      out[..., 2] + out[..., 3],
                      out[..., 4]], axis=-1)
    return jnp.transpose(hist, (2, 0, 1, 3))[:, :f, :num_bins, :], rows


def build_histogram_pallas_leaves(bins_t: jnp.ndarray, w8: jnp.ndarray,
                                  ch: jnp.ndarray, *, num_bins: int,
                                  row_block: int = DEFAULT_ROW_BLOCK,
                                  interpret: bool = None,
                                  pipeline: str = None,
                                  bins_packed: bool = False,
                                  compact: bool = False):
    """(LEAF_CHANNELS, F, B, 3) histograms of 25 leaf channels in one pass.

    Args:
      bins_t: (F, N) integer bin codes — or, with ``bins_packed``, the
        (F, N//2) nibble-packed bytes from :func:`pack_bins4`.  N must be
        a multiple of ``row_block``.
      w8: (8, N) bf16 FEATURE-MAJOR weight rows from :func:`pack_weights8`.
      ch: (N,) integer leaf channel in [0, LEAF_CHANNELS), or -1 for rows
        that belong to no batched leaf (they contribute nothing).
      num_bins: static global bin count B.
      interpret / pipeline / bins_packed: as :func:`build_histogram_pallas`.
      compact: the caller knows ``ch`` to be mostly -1 (a wave's or the
        endgame's smaller children; any pass of a sampled tree, whose
        out-of-bag rows carry -1).  The ``dma`` pipeline then moves the
        active rows to the front (:func:`_compact_rows_dma`) and contracts
        the row blocks that hold them; the result is ``(hist, counts)``
        with ``counts`` (4,) int32: the rows the kernel looped over, the
        active lanes, the compaction blocks and those of them that hold
        an active lane (under ``blockspec`` and nibble-packed bins, which
        keep the dense form: :func:`dense_pass_counts`).  f32 sums may
        differ from the dense pass's in the last bit: the same products
        meet in other row blocks.
    """
    f, np_ = bins_t.shape
    n = np_ * 2 if bins_packed else np_
    _check_rows(n, row_block, "build_histogram_pallas_leaves")
    _check_same_rows("build_histogram_pallas_leaves", n, w8=w8.shape[1],
                     ch=ch.shape[0])
    pipeline = resolve_pipeline(pipeline)
    interpret = resolve_interpret(interpret)
    if bins_packed:
        if num_bins > PACK4_MAX_BINS:
            raise ValueError(f"bins_packed requires num_bins <= "
                             f"{PACK4_MAX_BINS}, got {num_bins}")
        pipeline = "dma"
    rows = (_leaves_dma_tiling(f, num_bins, _LEAVES_M_CAP)[4:]
            if pipeline == "dma" else ())             # (f_pad, fc)
    _note_kernel(f"ops/hist_kernel/leaves/{pipeline}"
                 + ("/packed4" if bins_packed else ""),
                 f * np_ * bins_t.dtype.itemsize + n * (_C * 2 + 4) +
                 LEAF_CHANNELS * f * num_bins * 3 * 4, *rows)
    compacts = compact and pipeline == "dma" and not bins_packed
    if pipeline == "dma":
        hist, rows = _build_histogram_pallas_leaves_dma(
            bins_t, w8, ch, num_bins=num_bins, row_block=row_block,
            interpret=interpret, packed=bins_packed, compact=compacts)
    else:
        hist = _build_histogram_pallas_leaves_bs(
            bins_t, w8, ch, num_bins=num_bins, row_block=row_block,
            interpret=interpret)
    if not compact:
        return hist
    return hist, (rows if compacts else dense_pass_counts(n, ch))


# ---------------------------------------------------------------------------
# Quantized-gradient kernel: int8 x int8 -> int32 on the MXU, 42 leaves/pass.
#
# The TPU analog of LightGBM 4.x gradient quantization (reference:
# src/treelearner/gradient_discretizer.cpp DiscretizeGradients — int8
# stochastic-rounded gradients feeding integer histograms).  Quantized
# gradients need only THREE lanes per leaf (g_q, h_q, count — no hi/lo
# exactness pairs: integer sums in the int32 MXU accumulator are exact by
# construction), so 42 leaves share one pass vs the bf16 kernel's 25, and
# the i8 MXU path runs at twice the bf16 MAC rate on v5e.  Histogram
# subtraction (parent - child) is exact integer arithmetic — strictly
# better conditioned than the reference's f64 CPU path.  Exactness bounds
# per int32 accumulator bin: the count channel (weight 1) is exact to 2^31
# rows/shard; the g_q/h_q channels (weights up to gq_max/hq_max) are exact
# to 2^31/gq_max rows of ONE leaf landing in ONE bin per shard (~16.9M
# rows at 127 levels); where a data set's fullest bin can pass that, the
# pass is summed in segments and the sums kept as two limbs (``acc_rows``;
# ops/quantize.py has the bound and who decides).  The bf16 kernel's f32
# counts cap at 2^24 (ops/histogram.py).
#
# Mosaic constraints probed on v5e (scripts/proto_q8_*.py): 8-bit compares
# and 8-bit elementwise multiplies are NOT supported — the one-hot and the
# lane-expanded weights are built with 32-bit arithmetic and packed to i8
# right before the dot.  Best measured layout (proto_q8_round2.py at
# 10.5M x 28 x 256): FEATURE-MAJOR (8, N) weights consumed as a
# (128, R) right operand with the dot contracting dim 1 of both sides —
# 72 ms/pass vs 108 ms for the row-major (R, 128) form and 164 ms for
# the bf16 25-leaf kernel; group=8 features per contraction (M=2048),
# kr=4096 row blocks.  The feature-major layout also makes the per-wave
# leaf-channel update a contiguous (N,) row write instead of a strided
# lane update.
# ---------------------------------------------------------------------------


def _hist_leaves_q8_kernel(bins_ref, wch_ref, ch_ref, out_ref, *,
                           num_features: int, num_bins: int, group: int):
    """Accumulate (F*B, 128) lane-packed int32 leaf histograms over one
    row block (42 leaves x 3 int8 channels in the 128-lane dimension)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    wch = wch_ref[...]                   # (8, R) i8: g_q, h_q, cnt, 0*5
    r = wch.shape[1]
    b = num_bins
    ch = ch_ref[...].astype(jnp.int32)   # (1, R); -1 = inactive
    subl = jax.lax.broadcasted_iota(jnp.int32, (128, r), 0)
    sel = (ch == subl // _QCB).astype(jnp.int32)
    w3 = wch[:_QCB, :].astype(jnp.int32)           # (3, R)
    wtile = jnp.concatenate([w3] * (128 // _QCB + 1), axis=0)[:128]
    w128t = (wtile * sel).astype(jnp.int8)         # (128, R)
    iota_gb = jax.lax.broadcasted_iota(jnp.int32, (group * b, r), 0) % b

    for k in range(num_features // group):
        cols = bins_ref[k * group:(k + 1) * group, :].astype(jnp.int32)
        colrep = jnp.repeat(cols, b, axis=0)                 # (g*B, R)
        onehot = (colrep == iota_gb).astype(jnp.int8)
        part = jax.lax.dot_general(
            onehot, w128t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)                # (g*B, 128)
        out_ref[k * group * b:(k + 1) * group * b] += part


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_block", "interpret"))
def _build_histogram_pallas_leaves_q8_bs(bins_t: jnp.ndarray,
                                         wch: jnp.ndarray,
                                         ch: jnp.ndarray, *, num_bins: int,
                                         row_block: int = DEFAULT_ROW_BLOCK,
                                         interpret: bool = False
                                         ) -> jnp.ndarray:
    """Implicit-pipeline (BlockSpec-fetched) 42-leaf q8 kernel (v1)."""
    _, n = wch.shape
    f = bins_t.shape[0]
    b = _round_up(num_bins, 64)
    # largest power-of-two feature group with (g*b) % 128 == 0 and the
    # stacked one-hot M dim capped at 2048 (measured best at B=256)
    group = 1
    while (group * 2 * b <= 2048 and (group * 2 * b) % 128 == 0
           and group * 2 <= max(f, 1)) or (group * b) % 128 != 0:
        group *= 2
        if group > 128:
            raise ValueError(f"num_bins={num_bins} unsupported")
    ft_cap = max(group, 8192 // b // group * group)
    ft = min(_round_up(f, group), ft_cap)
    f_pad = _round_up(f, ft)
    if f_pad != f:
        bins_t = jnp.pad(bins_t, ((0, f_pad - f), (0, 0)))
    kr = math.gcd(row_block, 4096)

    grid = (f_pad // ft, n // kr)
    out = pl.pallas_call(
        functools.partial(_hist_leaves_q8_kernel, num_features=ft,
                          num_bins=b, group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ft, kr), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, kr), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, kr), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ft * b, 128), lambda i, j: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f_pad * b, 128), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=2 * f_pad * b * n * 128,
            bytes_accessed=f_pad * n + n * 9 + f_pad * b * 512,
            transcendentals=0),
        interpret=interpret,
        name=_kname("hist_leaves_q8_blockspec", f=f_pad, b=b, g=group,
                    kr=kr, n=n),
    )(bins_t, wch, ch.astype(jnp.int8).reshape(1, n))

    out = out[:, :Q_LEAF_CHANNELS * _QCB].reshape(f_pad, b,
                                                  Q_LEAF_CHANNELS, _QCB)
    return jnp.transpose(out, (2, 0, 1, 3))[:, :f, :num_bins, :]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_block", "interpret",
                                    "packed", "compact", "acc_rows"))
def _build_histogram_pallas_leaves_q8_dma(bins_t, wch, ch, *, num_bins,
                                          row_block, interpret, packed,
                                          compact=False, acc_rows=0):
    n = wch.shape[1]
    # int32 channel row: Mosaic cannot slice a (1, kr) slab out of a
    # one-row int8 array (its tiles are 4 sublanes deep)
    ch2 = ch.astype(jnp.int32).reshape(1, n)
    out, f_pad, rows = _leaves_dma_call(
        bins_t, wch, ch2, kind="hist_leaves_q8", num_bins=num_bins,
        interpret=interpret,
        packed=packed, m_cap=_LEAVES_Q8_M_CAP, kr0=4096,
        make_w128=_make_w128_q8,
        onehot_dtype=jnp.int8, acc_dtype=jnp.int32,
        out_dtype=jnp.int32, row_block=row_block, compact=compact,
        acc_rows=acc_rows)
    f = bins_t.shape[0]

    def leaves(out):
        b = out.shape[0] // f_pad
        out = out[:, :Q_LEAF_CHANNELS * _QCB].reshape(f_pad, b,
                                                      Q_LEAF_CHANNELS, _QCB)
        return jnp.transpose(out, (2, 0, 1, 3))[:, :f, :num_bins, :]

    if acc_rows:
        return hist_limbs([leaves(o) for o in out]), rows
    return leaves(out), rows


def build_histogram_pallas_leaves_q8(bins_t: jnp.ndarray, wch: jnp.ndarray,
                                     ch: jnp.ndarray, *, num_bins: int,
                                     row_block: int = DEFAULT_ROW_BLOCK,
                                     interpret: bool = None,
                                     pipeline: str = None,
                                     bins_packed: bool = False,
                                     compact: bool = False,
                                     acc_rows: int = 0):
    """(Q_LEAF_CHANNELS, F, B, 3) int32 histograms of 42 leaf channels.

    Args:
      bins_t: (F, N) uint8 bin codes — or, with ``bins_packed``, the
        (F, N//2) nibble-packed bytes from :func:`pack_bins4`.  N must be
        a multiple of ``row_block``.
      wch: (8, N) int8 FEATURE-MAJOR rows [g_q, h_q, count, 0*5] —
        static per tree (quantize once; no per-wave rewrite).
      ch: (N,) int8 leaf channel in [0, Q_LEAF_CHANNELS), or -1 for
        inactive rows (they contribute nothing regardless of their
        weight lanes).
      num_bins: static global bin count B (<= 256).
      interpret / pipeline / bins_packed: as :func:`build_histogram_pallas`.
      compact: as :func:`build_histogram_pallas_leaves`; the result is
        then ``(hist, counts)``.
      acc_rows: 0, or the most rows that may be added into one int32
        (ops/quantize.py ``hist_acc_rows``, a multiple of ``row_block``):
        the pass is then summed in segments of that many rows (``dma``:
        the same operands, each call told its first block; ``blockspec``:
        row slices) and the result is the segments' exact sum as
        (42, F, B, 5) int32 limbs (ops/quantize.py ``hist_limbs``).
    Returns:
      (42, F, B, 3) int32: channel sums (sum g_q, sum h_q, count) —
      exact integer sums, so every pipeline/packing variant, compacted
      or not, is bit-for-bit identical.
    """
    f, np_ = bins_t.shape
    n = np_ * 2 if bins_packed else np_
    _check_rows(n, row_block, "build_histogram_pallas_leaves_q8")
    _check_same_rows("build_histogram_pallas_leaves_q8", n,
                     wch=wch.shape[1], ch=ch.shape[0])
    pipeline = resolve_pipeline(pipeline)
    interpret = resolve_interpret(interpret)
    if bins_packed:
        if num_bins > PACK4_MAX_BINS:
            raise ValueError(f"bins_packed requires num_bins <= "
                             f"{PACK4_MAX_BINS}, got {num_bins}")
        pipeline = "dma"
    rows = (_leaves_dma_tiling(f, num_bins, _LEAVES_Q8_M_CAP)[4:]
            if pipeline == "dma" else ())             # (f_pad, fc)
    _note_kernel(f"ops/hist_kernel/leaves_q8/{pipeline}"
                 + ("/packed4" if bins_packed else ""),
                 f * np_ * bins_t.dtype.itemsize + n * 9 +
                 Q_LEAF_CHANNELS * f * num_bins * 3 * 4, *rows)
    compacts = compact and pipeline == "dma" and not bins_packed
    if pipeline == "dma":
        hist, rows = _build_histogram_pallas_leaves_q8_dma(
            bins_t, wch, ch, num_bins=num_bins, row_block=row_block,
            interpret=interpret, packed=bins_packed,
            compact=compacts, acc_rows=acc_rows)
    elif acc_rows:
        acc_rows = max(row_block, acc_rows // row_block * row_block)
        hist = hist_limbs([
            _build_histogram_pallas_leaves_q8_bs(
                bins_t[:, lo:lo + acc_rows], wch[:, lo:lo + acc_rows],
                ch[lo:lo + acc_rows], num_bins=num_bins,
                row_block=row_block, interpret=interpret)
            for lo in range(0, n, acc_rows)])
    else:
        hist = _build_histogram_pallas_leaves_q8_bs(
            bins_t, wch, ch, num_bins=num_bins, row_block=row_block,
            interpret=interpret)
    if not compact:
        return hist
    return hist, (rows if compacts else dense_pass_counts(n, ch))


# ---------------------------------------------------------------------------
# Wave row update: one fused pass assigning rows to their post-wave leaf
# and leaf channel.  The XLA form (learner/wave.py's W sequential masked
# wheres) launches ~W fused loop nests over N rows — per-nest overhead
# alone costs ~30 ms/wave at 10.5M rows.  Here ONE kernel sweeps the rows,
# keeping rl/ch blocks VMEM-resident across the W per-split updates.  The
# ``dma`` pipeline fetches the W winning features' bin columns itself: it
# is handed a feature-major view of the whole bin matrix
# (:func:`bin_rows_view`) and the W feature ids (row 7 of the split table,
# in SMEM), and copies each row block of each winning feature straight
# from that view into VMEM — nothing of shape (W, N) is built in HBM.  A
# caller that already holds the W columns passes them as the matrix, with
# feature ids 0..W-1: the same kernel.  The ``blockspec`` pipeline still
# gathers the columns in front of its kernel (:func:`gather_bin_rows`).
# A categorical split's left set rides with the table as a 256-bit set
# (eight SMEM words a slot, the word picked by seven selects on ``bin >>
# 5``): no per-row gather.  A slot whose split feature lives in an EFB
# bundle is such a slot too: its set holds the bundle column's codes that go
# left (efb.py ``bundle_left_sets``), and the kernel's name says ``_efb``.
# wave.py keeps the XLA path for more than 255 bins.
# ---------------------------------------------------------------------------

_RU_SUB = 8      # the row update lays rows out as (_RU_SUB, N // _RU_SUB)
_RU_TAB = 8      # scalar rows of the split table; a categorical table adds
_RU_WORDS = 8    # is_cat and this many 32-bit words of left-set bins
_RU_LANES = 512  # lanes swept at a time: rl, ch and a column in 12 vregs
# Rows per fetched block of the dma pipeline, at most: a block costs W
# column copies, and at 4096 rows (4 KB a copy) issuing them shows — at
# 21.25M rows x 67, W=42 a call took 9.6 ms at 4096, 7.6 at 8192, 6.9 at
# 16384 (PERF.md, PR 29).  The block is the largest power of two up to
# this that divides N.
_RU_KR = 16384


def bin_rows_view(bins_t: jnp.ndarray, pipeline: str = None) -> jnp.ndarray:
    """What :func:`wave_row_update_pallas` fetches a tree's columns from,
    made ONCE per tree and passed to every call with the splits' feature
    ids.  Under the ``dma`` pipeline: (F, N) bin codes as (F, 8, N // 8),
    so the feature is the LEADING, untiled axis and one feature's row
    block is one contiguous strip a DMA can address by a runtime feature
    id (on a TPU a relayout: one copy of the matrix).  The ``blockspec``
    pipeline gathers from the matrix as it is."""
    if resolve_pipeline(pipeline) != "dma":
        return bins_t
    f, n = bins_t.shape
    return bins_t.reshape(f, _RU_SUB, n // _RU_SUB)


def gather_bin_rows(bins_t: jnp.ndarray, feats: jnp.ndarray) -> jnp.ndarray:
    """``bins_t[feats]`` for a static-length ``feats``, as a stack of
    one-row dynamic slices.  A row gather (``jnp.take(bins_t, feats,
    axis=0)``) of a (F, N) matrix costs XLA:TPU compile time in
    proportion to N — ~35 s per gather at 10.5M rows (PERF.md, PR 21);
    the slices move the same bytes and compile in constant time.  Only
    the paths the fetching kernel does not serve pay for it (nibble-packed
    bins, the ``blockspec`` pipeline)."""
    width = bins_t.shape[1]
    return jnp.concatenate(
        [jax.lax.dynamic_slice(bins_t, (feats[j], 0), (1, width))
         for j in range(feats.shape[0])], axis=0)


def _row_update_sweep(col_of, rl, tab_ref, w: int, cat: bool = False):
    """The W splits applied one after the other to a block of rows.
    ``cat``: the table carries, below its eight rows, a slot's ``is_cat``
    flag (row 8) and the 256-bit set of the bins that go left as eight
    words (rows 9..16, bit ``b & 31`` of word ``b >> 5``); a categorical
    slot tests membership where a numeric one compares."""
    ch = jnp.full_like(rl, -1)
    for j in range(w):
        col = col_of(j).astype(jnp.int32)
        thr = tab_ref[0, j]
        nanb = tab_ref[1, j]
        dlft = tab_ref[2, j]
        small = tab_ref[3, j]
        selj = tab_ref[4, j]
        newid = tab_ref[5, j]
        act = tab_ref[6, j]
        # integer-valued go_left: Mosaic cannot broadcast a scalar bool
        # through a packed vector (i8->i1 trunci), so the select stays in
        # int32 land and the flags compare as integers
        go_left = jnp.where(col == nanb, dlft,
                            (col <= thr).astype(jnp.int32))
        if cat:
            hi = col >> 5
            word = tab_ref[_RU_TAB + 8, j]
            for k in range(6, -1, -1):      # the word picked by 7 selects
                word = jnp.where(hi == k, tab_ref[_RU_TAB + 1 + k, j], word)
            member = (word >> (col & 31)) & 1
            go_left = jnp.where(
                jnp.full_like(col, tab_ref[_RU_TAB, j]) > 0, member, go_left)
        upd = (rl == selj) & (act > 0)
        ch = jnp.where(upd & (go_left == small), j, ch)
        rl = jnp.where(upd & (go_left == 0), newid, rl)
    return rl, ch


def _row_update_kernel(cols_ref, rl_ref, tab_ref, rl_out, ch_out, *,
                       w: int, cat: bool = False):
    rl, ch = _row_update_sweep(lambda j: cols_ref[j],
                               rl_ref[...].astype(jnp.int32), tab_ref, w,
                               cat)
    rl_out[...] = rl
    ch_out[...] = ch.astype(jnp.int8)


def _row_update_kernel_dma(bins_hbm, rl_hbm, tab_ref, rl_out, ch_out, *,
                           w: int, krd: int, nsteps: int,
                           cat: bool = False):
    """Fully manual DMA pipeline of the wave row update: per row block,
    W copies bring the winning features' strips ``bins_hbm[tab[7, jj], :,
    block]`` (the feature ids ride in the split table's eighth row) and
    one brings the row->leaf block, into double buffers; the updated
    rl/ch blocks stream back out, and the copies of block j+1 overlap
    block j's W-split sweep — the kernel is pure VPU work, so it is
    bandwidth-bound end to end."""

    # a block wider than _RU_LANES is swept that many lanes at a time, so
    # rl, ch and the column stay in registers across the W splits
    lw = min(krd, _RU_LANES)

    def body(cbuf, ibuf, robuf, cobuf, csem, isem, rosem, cosem):
        def col_dma(slot, j, jj):
            # W copies share the slot's semaphore: started W times,
            # waited W times
            return pltpu.make_async_copy(
                bins_hbm.at[tab_ref[7, jj], :, pl.ds(j * krd, krd)],
                cbuf.at[slot, jj], csem.at[slot])

        def rl_dma(slot, j):
            return pltpu.make_async_copy(
                rl_hbm.at[:, pl.ds(j * krd, krd)], ibuf.at[slot],
                isem.at[slot])

        def ro_dma(slot, j):
            return pltpu.make_async_copy(
                robuf.at[slot], rl_out.at[:, pl.ds(j * krd, krd)],
                rosem.at[slot])

        def co_dma(slot, j):
            return pltpu.make_async_copy(
                cobuf.at[slot], ch_out.at[:, pl.ds(j * krd, krd)],
                cosem.at[slot])

        def start_in(slot, j):
            for jj in range(w):
                col_dma(slot, j, jj).start()
            rl_dma(slot, j).start()

        start_in(0, 0)

        def step(j, carry):
            slot = j % 2

            @pl.when(j + 1 < nsteps)
            def _():
                start_in((j + 1) % 2, j + 1)

            for jj in range(w):
                col_dma(slot, j, jj).wait()
            rl_dma(slot, j).wait()

            # the out buffers double-buffer too: wait this slot's
            # previous write-back before overwriting it
            @pl.when(j >= 2)
            def _():
                ro_dma(slot, j - 2).wait()
                co_dma(slot, j - 2).wait()

            def sweep(c, carry):
                lanes = (slice(None) if lw == krd else
                         pl.ds(pl.multiple_of(c * lw, lw), lw))
                rl, ch = _row_update_sweep(
                    lambda jj: cbuf[slot, jj, :, lanes],
                    ibuf[slot, :, lanes].astype(jnp.int32), tab_ref, w,
                    cat)
                robuf[slot, :, lanes] = rl
                cobuf[slot, :, lanes] = ch.astype(jnp.int8)
                return carry

            if lw == krd:
                sweep(0, 0)
            else:
                jax.lax.fori_loop(0, krd // lw, sweep, 0)
            ro_dma(slot, j).start()
            co_dma(slot, j).start()
            return carry

        jax.lax.fori_loop(0, nsteps, step, 0)
        # drain the last two in-flight write-backs
        if nsteps >= 2:
            ro_dma((nsteps - 2) % 2, nsteps - 2).wait()
            co_dma((nsteps - 2) % 2, nsteps - 2).wait()
        ro_dma((nsteps - 1) % 2, nsteps - 1).wait()
        co_dma((nsteps - 1) % 2, nsteps - 1).wait()

    pl.run_scoped(body,
                  pltpu.VMEM((2, w, _RU_SUB, krd), bins_hbm.dtype),
                  pltpu.VMEM((2, _RU_SUB, krd), rl_hbm.dtype),
                  pltpu.VMEM((2, _RU_SUB, krd), jnp.int32),
                  pltpu.VMEM((2, _RU_SUB, krd), jnp.int8),
                  pltpu.SemaphoreType.DMA((2,)),
                  pltpu.SemaphoreType.DMA((2,)),
                  pltpu.SemaphoreType.DMA((2,)),
                  pltpu.SemaphoreType.DMA((2,)))


def _set_suffix(cat: bool, tag: str) -> str:
    """The part of a row-update kernel's name that says which slots carry
    left sets: ``_cat`` categorical ones, ``_efb`` bundled ones."""
    return tag if tag else ("_cat" if cat else "")


@functools.partial(jax.jit, static_argnames=("interpret", "cat", "tag"))
def _wave_row_update_dma(bins3: jnp.ndarray, rl: jnp.ndarray,
                         tab: jnp.ndarray, *, interpret: bool = False,
                         cat: bool = False, tag: str = ""):
    """``bins3``: a :func:`bin_rows_view`; ``tab[7]``: W ids into its
    leading axis; ``cat``: ``tab`` is a :func:`_cat_table`; ``tag``: the
    sets' kind in the kernel's name, where not ``_cat``."""
    f, _, nd = bins3.shape
    w = tab.shape[1]
    n = nd * _RU_SUB
    kr = math.gcd(n, _RU_KR)
    krd = kr // _RU_SUB
    rl2 = rl.astype(jnp.int32).reshape(_RU_SUB, nd)
    rl_new, ch = pl.pallas_call(
        functools.partial(_row_update_kernel_dma, w=w, krd=krd,
                          nsteps=n // kr, cat=cat),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((_RU_SUB, nd), jnp.int32),
            jax.ShapeDtypeStruct((_RU_SUB, nd), jnp.int8),
        ],
        interpret=interpret,
        name=_kname("wave_row_update_dma" + _set_suffix(cat, tag),
                    w=w, f=f, kr=kr, n=n),
    )(bins3, rl2, tab)
    return rl_new.reshape(n), ch.reshape(n)


@functools.partial(jax.jit,
                   static_argnames=("row_block", "interpret", "cat", "tag"))
def _wave_row_update_bs(cols_w: jnp.ndarray, rl: jnp.ndarray,
                        tab: jnp.ndarray, *,
                        row_block: int = DEFAULT_ROW_BLOCK,
                        interpret: bool = False, cat: bool = False,
                        tag: str = ""):
    """Implicit-pipeline (BlockSpec-fetched) row update (v1 layout)."""
    w, n = cols_w.shape
    kr = math.gcd(row_block, 4096)
    krd = kr // 8
    nd = n // 8
    cols3 = cols_w.reshape(w, 8, nd)
    rl2 = rl.astype(jnp.int32).reshape(8, nd)

    grid = (n // kr,)
    rl_new, ch = pl.pallas_call(
        functools.partial(_row_update_kernel, w=w, cat=cat),
        grid=grid,
        in_specs=[
            pl.BlockSpec((w, 8, krd), lambda i: (0, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, krd), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((8, krd), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, krd), lambda i: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, nd), jnp.int32),
            jax.ShapeDtypeStruct((8, nd), jnp.int8),
        ],
        interpret=interpret,
        name=_kname("wave_row_update_blockspec" + _set_suffix(cat, tag),
                    w=w, kr=kr, n=n),
    )(cols3, rl2, tab)
    return rl_new.reshape(n), ch.reshape(n)


def _cat_table(tab: jnp.ndarray, is_cat: jnp.ndarray,
               member: jnp.ndarray) -> jnp.ndarray:
    """The (8, W) split table with the categorical slots' tests below it:
    row 8 ``is_cat``, rows 9..16 the (W, B <= 256) left-set membership
    ``member`` as eight 32-bit words a slot."""
    w, b = member.shape
    bits = jnp.pad(member, ((0, 0), (0, 32 * _RU_WORDS - b))).reshape(
        w, _RU_WORDS, 32).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return jnp.concatenate([
        tab, is_cat.astype(jnp.int32)[None, :],
        jax.lax.bitcast_convert_type(words, jnp.int32).T])


def wave_row_update_pallas(bins: jnp.ndarray, rl: jnp.ndarray,
                           tab: jnp.ndarray, *, feats: jnp.ndarray = None,
                           cat: tuple = None, bundled: bool = False,
                           row_block: int = DEFAULT_ROW_BLOCK,
                           interpret: bool = None, pipeline: str = None):
    """Apply a wave's W splits to every row in one fused pass.

    Args:
      bins: where the splits' bin columns come from, N a multiple of
        ``row_block``.  Without ``feats``: (W, N) uint8, the W winning
        feature columns themselves, in the splits' order.  With ``feats``:
        the feature-major bin matrix, as (F, N) uint8 or (the form to make
        once and pass to every call of a tree) its :func:`bin_rows_view`
        (F, 8, N // 8); the ``dma`` kernel then fetches the W rows it
        needs itself and no (W, N) array exists.
      rl: (N,) integer row->leaf vector (any integer dtype).
      tab: (8, W) int32 per-split table: rows are [threshold_bin,
        nan_bin (-1 = none), default_left, left_is_smaller, split_leaf,
        new_right_id, active, (overwritten: the feature ids)].
      feats: (W,) integer feature id of each split (clipped to [0, F)),
        or None when ``bins`` holds the columns.
      cat: None where every split is numeric (``bin <= threshold``, one
        NaN bin with its direction), else ``(is_cat (W,) bool, member
        (W, B <= 256) bool)``: a categorical slot sends a row left iff its
        bin is in the slot's set, whatever the slot's threshold and NaN
        bin say; the kernel is then named ``..._cat_...``.
      bundled: the data set is EFB-bundled: ``bins`` holds bundle columns,
        ``feats`` bundle ids, and ``cat`` marks the slots whose split
        feature is a bundle member too, their sets in bundle codes
        (efb.py ``bundle_left_sets``); the kernel is named ``..._efb_...``
        (``..._cat_efb_...`` never: one name for a bundled data set).
      interpret / pipeline: as :func:`build_histogram_pallas` ("dma"
        streams the column blocks AND the rl/ch write-backs through
        double-buffered async copies).
    Returns:
      (rl_new int32 (N,), ch int8 (N,)) — post-wave leaf ids and the
      smaller-child channel (-1 = row not in any split's smaller child).
    """
    w = tab.shape[1]
    fetch = feats is not None
    f = bins.shape[0]
    n = math.prod(bins.shape[1:])
    if not fetch and f != w:
        raise ValueError(f"wave_row_update_pallas: {f} columns for {w} "
                         f"splits (pass feats= with a bin matrix)")
    _check_rows(n, row_block, "wave_row_update_pallas")
    _check_same_rows("wave_row_update_pallas", n, rl=rl.shape[0])
    pipeline = resolve_pipeline(pipeline)
    interpret = resolve_interpret(interpret)
    _note_kernel(f"ops/hist_kernel/row_update/{pipeline}"
                 + ("/fetch" if fetch and pipeline == "dma" else "")
                 + ("/efb" if bundled else "/cat" if cat is not None else ""),
                 w * n * bins.dtype.itemsize + n * 4 + n * 5)
    if fetch:
        feats = jnp.clip(feats.astype(jnp.int32), 0, f - 1)
    is_cat = cat is not None
    tag = "_efb" if bundled else ""
    if pipeline == "dma":
        if bins.ndim == 2:
            bins = bin_rows_view(bins, pipeline)
        ids = feats if fetch else jnp.arange(w, dtype=jnp.int32)
        tab = tab.at[7].set(ids)
        return _wave_row_update_dma(
            bins, rl, _cat_table(tab, *cat) if is_cat else tab,
            interpret=interpret, cat=is_cat, tag=tag)
    if fetch:
        bins = gather_bin_rows(bins.reshape(f, n), feats)
    return _wave_row_update_bs(
        bins, rl, _cat_table(tab, *cat) if is_cat else tab,
        row_block=row_block, interpret=interpret, cat=is_cat, tag=tag)


def wave_trial_channels_pallas(bins: jnp.ndarray, rl: jnp.ndarray,
                               sel_leaves: jnp.ndarray, thr: jnp.ndarray,
                               nan_bin: jnp.ndarray, default_left: jnp.ndarray,
                               left_smaller: jnp.ndarray, active: jnp.ndarray,
                               *, feats: jnp.ndarray = None,
                               row_block: int = DEFAULT_ROW_BLOCK,
                               interpret: bool = None,
                               pipeline: str = None) -> jnp.ndarray:
    """TRIAL leaf-channel assignment for W *candidate* splits.

    Same fused kernel as :func:`wave_row_update_pallas` (``bins`` /
    ``feats`` as there), but the splits are NOT committed: each
    candidate's ``new_right_id`` is set to its own split leaf, so ``rl``
    is provably unchanged and only the smaller-child channel vector comes
    back.  The wave grower's exact endgame uses this to precompute the
    frontier candidates' smaller-child histograms in one batched pass
    before the sequential best-first selection commits any of them
    (learner/wave.py).

    Returns ``ch`` int8 (N,): the candidate slot whose smaller side the
    row would take, or -1.
    """
    tab = jnp.stack([thr, nan_bin, default_left.astype(jnp.int32),
                     left_smaller.astype(jnp.int32), sel_leaves, sel_leaves,
                     active.astype(jnp.int32), jnp.zeros_like(thr)])
    _, ch = wave_row_update_pallas(bins, rl, tab, feats=feats,
                                   row_block=row_block,
                                   interpret=interpret, pipeline=pipeline)
    return ch


# ---------------------------------------------------------------------------
# Score update: ``score + leaf_value[row_leaf]`` for the training rows,
# once a tree.  As an XLA gather the lookup costs 8.2 ns a row whatever
# the table's size (174 ms at 21.25M rows: PERF.md, PR 35); here it is a
# streaming pass over the rows that picks each row's value out of the
# table by compares — ``acc = where(rl == l, lv[l], acc)`` for every leaf
# l, the table in SMEM, ``row_leaf`` and the selected value in registers
# across the chain.  A select hands one f32 value on untouched, so the
# result has the gather's bits.  The arrays keep their own 1-D layout (no
# relayout in front of the kernel), the score is written where it was
# read, and a last block that the rows do not fill is masked by the
# pipeline: the score keeps the data set's own length, ``row_leaf`` may
# carry the grower's padded rows behind it.
# ---------------------------------------------------------------------------

# At 21.25M rows x 255 leaves on a v5e (PERF.md, PR 35): 2.03-2.22 ms at
# 32768-262144 rows a step, 8192-16384 rows a sweep and 32-254 leaves a
# trip; 2.39 / 2.86 ms at 4096 / 2048 rows a sweep.
_SU_KR = 65536    # rows per grid step
_SU_LANES = 8192  # rows swept at a time: row_leaf and the value in 16 vregs
_SU_GROUP = 128   # leaves per trip of the chain's loop, unrolled


def _score_update_kernel(lv_ref, rl_ref, score_ref, out_ref, *, leaves: int,
                         kr: int, lanes: int, group: int):
    trips = (leaves - 1) // group

    def sweep(c, carry):
        at = pl.ds(pl.multiple_of(c * lanes, lanes), lanes)
        rl = rl_ref[at]

        def pick(first, count, acc):
            for l in range(count):
                acc = jnp.where(rl == first + l, lv_ref[first + l], acc)
            return acc

        # leaf 0 is the chain's start, so an id outside the table reads
        # leaf 0 (the grower hands none out)
        value = jnp.full(rl.shape, lv_ref[0], jnp.float32)
        done = 1
        if trips > 1:   # a single trip is unrolled with the remainder
            value = jax.lax.fori_loop(
                0, trips, lambda t, acc: pick(1 + t * group, group, acc),
                value)
            done += trips * group
        value = pick(done, leaves - done, value)
        out_ref[at] = score_ref[at] + value
        return carry

    jax.lax.fori_loop(0, kr // lanes, sweep, 0)


def _tile_1d(n: int) -> int:
    """XLA's tile of a 1-D 32-bit array on a TPU: 1024 elements, for a
    shorter array the next power of two from 128 up."""
    return min(1024, max(128, 1 << (n - 1).bit_length()))


def score_update_pallas(score: jnp.ndarray, row_leaf: jnp.ndarray,
                        leaf_value: jnp.ndarray, *,
                        interpret: bool = None) -> jnp.ndarray:
    """``score + leaf_value[row_leaf[:N]]`` with the lookup as a select
    chain over the leaves, in place where the caller donates ``score``.

    Args:
      score: (N,) f32, any N.
      row_leaf: (>= N,) integer leaf id of every row, in [0, L); what lies
        behind the score's N rows is never read.
      leaf_value: (L,) f32.
    The cost is N x L compare-and-selects: the caller picks the gather
    where L is large (models/gbdt.py).
    """
    n, = score.shape
    leaves, = leaf_value.shape
    if row_leaf.shape[0] < n:
        raise ValueError(f"score_update_pallas: {row_leaf.shape[0]} leaf ids "
                         f"for {n} scores")
    # a block is whole tiles of every array it reads
    tile = _tile_1d(n)
    if _tile_1d(row_leaf.shape[0]) != tile:
        row_leaf = row_leaf[:n]
    lanes = min(_SU_LANES, _round_up(n, tile))
    kr = min(_SU_KR, _round_up(n, lanes))
    rows = pl.BlockSpec((kr,), lambda i: (i,))
    return pl.pallas_call(
        functools.partial(_score_update_kernel, leaves=leaves, kr=kr,
                          lanes=lanes, group=_SU_GROUP),
        grid=(pl.cdiv(n, kr),),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), rows, rows],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        input_output_aliases={2: 0},
        interpret=resolve_interpret(interpret),
        name=_kname("score_update", l=leaves, kr=kr, n=n),
    )(leaf_value.astype(jnp.float32), row_leaf.astype(jnp.int32),
      score.astype(jnp.float32))
