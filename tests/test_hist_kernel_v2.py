"""Histogram kernel v2 (ISSUE 8): interpret-mode tier-1 coverage of the
four Pallas kernels — DMA pipeline vs BlockSpec vs 4-bit-packed bins —
against the XLA reference impls, plus the vmap-to-grid batching rule,
the pad_rows() error contract, the packed4 XLA scatter, the autotune
disk cache and the hist_kernel telemetry site.

Shapes are deliberately tiny and SHARED across tests (the interpret
kernels compile once per (shape, variant) and the jit cache is
process-wide), keeping the file cheap inside the tier-1 budget."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops.histogram import build_histogram, build_histogram_leaves
from lightgbm_tpu.ops.histogram_pallas import (
    LEAF_CHANNELS, Q_LEAF_CHANNELS, _make_w128_bf16, build_histogram_pallas,
    build_histogram_pallas_leaves, build_histogram_pallas_leaves_q8,
    bin_rows_view, pack_bins4, pack_weights8, pad_rows, traced_kernels,
    unpack_bins4, wave_row_update_pallas, wave_trial_channels_pallas)

N, F = 4096, 5  # one exact row block — the boundary shape


def _data(n=N, f=F, B=16, seed=0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, (n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = np.abs(rng.randn(n)).astype(np.float32)
    # masked rows (w=0) must contribute nothing
    mask = (rng.rand(n) > 0.3).astype(np.float32)
    return bins, grad, hess, mask


# -- single-leaf kernel: every variant vs the XLA segment reference ----------

@pytest.mark.parametrize("B", [16, 64, 255])
def test_single_kernel_variants_vs_reference(B):
    bins, grad, hess, mask = _data(B=B)
    bt = jnp.asarray(bins.T.copy())
    g, h, m = map(jnp.asarray, (grad, hess, mask))
    ref = np.asarray(build_histogram(jnp.asarray(bins), g, h, m,
                                     num_bins=B, impl="segment"))
    scale = max(1.0, np.abs(ref).max())
    variants = [dict(pipeline="blockspec"), dict(pipeline="dma")]
    if B <= 16:
        variants.append(dict(bins_packed=True))
    outs = {}
    for kw in variants:
        src = pack_bins4(bt) if kw.get("bins_packed") else bt
        got = np.asarray(build_histogram_pallas(src, g, h, m,
                                                num_bins=B, **kw))
        name = "packed" if kw.get("bins_packed") else kw["pipeline"]
        outs[name] = got
        # f32 hi/lo exactness contract vs the f32 reference
        assert np.abs(got - ref).max() / scale < 1e-5, name
        # the count channel sums exact small integers — bitwise in any
        # accumulation order
        np.testing.assert_array_equal(got[..., 2], ref[..., 2], err_msg=name)


def test_single_kernel_n_plus_one_raises():
    bins, grad, hess, mask = _data(n=N + 1)
    with pytest.raises(ValueError, match="pad_rows"):
        build_histogram_pallas(jnp.asarray(bins.T.copy()),
                               jnp.asarray(grad), jnp.asarray(hess),
                               jnp.asarray(mask), num_bins=16)
    # row-aligned operand mismatch is caught by name
    bins, grad, hess, mask = _data()
    with pytest.raises(ValueError, match="grad"):
        build_histogram_pallas(jnp.asarray(bins.T.copy()),
                               jnp.asarray(grad[: N // 2]),
                               jnp.asarray(hess), jnp.asarray(mask),
                               num_bins=16)


def test_single_kernel_pad_boundary():
    """N=block data padded to 2 blocks with w=0 rows == unpadded build."""
    bins, grad, hess, mask = _data(B=16)
    bt = jnp.asarray(bins.T.copy())
    base = np.asarray(build_histogram_pallas(
        bt, jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(mask),
        num_bins=16))
    n2 = pad_rows(N + 1)
    assert n2 == 2 * N
    bp = jnp.asarray(np.pad(bins, ((0, n2 - N), (0, 0))).T.copy())
    padded = np.asarray(build_histogram_pallas(
        bp, jnp.asarray(np.pad(grad, (0, n2 - N))),
        jnp.asarray(np.pad(hess, (0, n2 - N))),
        jnp.asarray(np.pad(mask, (0, n2 - N))), num_bins=16))
    np.testing.assert_allclose(padded, base, rtol=1e-6, atol=1e-6)


def test_pack_bins4_roundtrip():
    bins, *_ = _data(B=16)
    bt = jnp.asarray(bins.T.copy())
    np.testing.assert_array_equal(np.asarray(unpack_bins4(pack_bins4(bt))),
                                  bins.T)


# -- leaf-batched kernels ----------------------------------------------------

def test_leaves_kernel_variants_vs_reference():
    bins, grad, hess, mask = _data(B=16, f=6)
    rng = np.random.RandomState(1)
    ch = rng.randint(-1, LEAF_CHANNELS, N).astype(np.int32)
    bt = jnp.asarray(bins.T.copy())
    g, h, m, chd = map(jnp.asarray, (grad, hess, mask, ch))
    w8 = pack_weights8(g, h, m)
    ref = np.asarray(build_histogram_leaves(
        jnp.asarray(bins), g, h, m, chd, num_channels=LEAF_CHANNELS,
        num_bins=16, impl="segment"))
    scale = max(1.0, np.abs(ref).max())
    for kw in [dict(pipeline="blockspec"), dict(pipeline="dma"),
               dict(bins_packed=True)]:
        src = pack_bins4(bt) if kw.get("bins_packed") else bt
        got = np.asarray(build_histogram_pallas_leaves(
            src, w8, chd, num_bins=16, **kw))
        assert np.abs(got - ref).max() / scale < 1e-5, kw
        np.testing.assert_array_equal(got[..., 2], ref[..., 2])


def test_q8_kernel_bitwise_across_variants():
    """Quantized path: int32 sums are exact — every pipeline/packing
    variant must agree bit-for-bit (the ISSUE 8 kernel contract)."""
    bins, _, _, mask = _data(B=16, f=6)
    rng = np.random.RandomState(2)
    wch = np.zeros((8, N), np.int8)
    act = (mask > 0)
    wch[0] = rng.randint(-127, 128, N) * act
    wch[1] = rng.randint(0, 128, N) * act
    wch[2] = act
    ch = rng.randint(-1, Q_LEAF_CHANNELS, N).astype(np.int8)
    bt = jnp.asarray(bins.T.copy())
    wchd, chd = jnp.asarray(wch), jnp.asarray(ch)
    base = np.asarray(build_histogram_pallas_leaves_q8(
        bt, wchd, chd, num_bins=16, pipeline="blockspec"))
    # reference check: histogram of channel 0 == per-leaf bincount
    want0 = np.zeros((16,), np.int64)
    sel = (ch == 0) & act
    for j in np.nonzero(sel)[0]:
        want0[bins[j, 0]] += int(wch[0, j])
    np.testing.assert_array_equal(base[0, 0, :, 0], want0)
    for kw in [dict(pipeline="dma"), dict(bins_packed=True)]:
        src = pack_bins4(bt) if kw.get("bins_packed") else bt
        got = np.asarray(build_histogram_pallas_leaves_q8(
            src, wchd, chd, num_bins=16, **kw))
        np.testing.assert_array_equal(got, base, err_msg=str(kw))


# -- ragged last feature tile (ISSUE 27): B=255 tiles the feature axis 32
# rows at a time, so F > 32 pads to 64 and the DMA leaf kernels skip the
# feature steps of the last tile that hold padding only ----------------------

RAGGED_N = 8192  # two row blocks


def _parent_leaves_dma_bf16(bt, w8, ch, *, num_bins=255):
    """The bf16 ``dma`` leaf kernel as it was before ISSUE 27, kept as the
    oracle: EVERY tile runs ``ft // fstep`` (4) feature steps, padding
    included.  Same DMAs, one-hot, contraction and accumulation order."""
    f, n = bt.shape
    b, group, fstep, ft, kr = 256, 4, 8, 32, 4096  # the tiling at B=255
    f_pad = -(-f // ft) * ft
    nsteps = n // kr

    def kernel(bins_hbm, w_hbm, ch_hbm, out_ref):
        out_ref[...] = jnp.zeros_like(out_ref)
        f0 = pl.program_id(0) * ft
        iota_gb = jax.lax.broadcasted_iota(jnp.int32, (group * b, kr), 0) % b

        def body(bbuf, wbuf, cbuf, bsem, wsem, csem):
            def dmas(slot, j):
                rows = pl.ds(j * kr, kr)
                return (pltpu.make_async_copy(
                            bins_hbm.at[pl.ds(f0, ft), rows], bbuf.at[slot],
                            bsem.at[slot]),
                        pltpu.make_async_copy(
                            w_hbm.at[:, rows], wbuf.at[slot], wsem.at[slot]),
                        pltpu.make_async_copy(
                            ch_hbm.at[:, rows], cbuf.at[slot], csem.at[slot]))

            for d in dmas(0, 0):
                d.start()

            def step(j, carry):
                slot = j % 2

                @pl.when(j + 1 < nsteps)
                def _():
                    for d in dmas((j + 1) % 2, j + 1):
                        d.start()

                for d in dmas(slot, j):
                    d.wait()
                w128t = _make_w128_bf16(wbuf[slot], cbuf[slot])

                def do(i, c):
                    fi = pl.multiple_of(i * fstep, fstep)
                    cols_blk = bbuf[slot, pl.ds(fi, fstep), :].astype(
                        jnp.int32)
                    for k in range(fstep // group):
                        cols = cols_blk[k * group:(k + 1) * group]
                        colrep = jnp.repeat(cols, b, axis=0)
                        onehot = (colrep == iota_gb).astype(jnp.bfloat16)
                        part = jax.lax.dot_general(
                            onehot, w128t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
                        out_ref[pl.ds((fi + k * group) * b,
                                      group * b)] += part
                    return c

                jax.lax.fori_loop(0, ft // fstep, do, 0)
                return carry

            jax.lax.fori_loop(0, nsteps, step, 0)

        pl.run_scoped(body,
                      pltpu.VMEM((2, ft, kr), bins_hbm.dtype),
                      pltpu.VMEM((2, 8, kr), w_hbm.dtype),
                      pltpu.VMEM((2, 1, kr), ch_hbm.dtype),
                      pltpu.SemaphoreType.DMA((2,)),
                      pltpu.SemaphoreType.DMA((2,)),
                      pltpu.SemaphoreType.DMA((2,)))

    out = pl.pallas_call(
        kernel, grid=(f_pad // ft,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec((ft * b, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((f_pad * b, 128), jnp.float32),
        interpret=True,
    )(jnp.pad(bt, ((0, f_pad - f), (0, 0))), w8,
      ch.astype(jnp.int32).reshape(1, n))
    out = out[:, :LEAF_CHANNELS * 5].reshape(f_pad, b, LEAF_CHANNELS, 5)
    hist = jnp.stack([out[..., 0] + out[..., 1], out[..., 2] + out[..., 3],
                      out[..., 4]], axis=-1)
    return jnp.transpose(hist, (2, 0, 1, 3))[:, :f, :num_bins, :]


@pytest.mark.parametrize("f", [33, 35, 40, 64])
def test_leaves_dma_ragged_last_tile(f):
    """q8: bitwise the ``blockspec`` kernel and a numpy scatter-add.
    bf16 hi/lo: bitwise the kernel that contracted the padding too, and
    inside the exactness budget of the f32 reference."""
    B, n = 255, RAGGED_N
    bins, grad, hess, mask = _data(n=n, f=f, B=B, seed=f)
    rng = np.random.RandomState(100 + f)
    bt = jnp.asarray(bins.T.copy())
    act = mask > 0

    wch = np.zeros((8, n), np.int8)
    wch[0] = rng.randint(-127, 128, n) * act
    wch[1] = rng.randint(0, 128, n) * act
    wch[2] = act
    chq = rng.randint(-1, Q_LEAF_CHANNELS, n).astype(np.int8)
    got = np.asarray(build_histogram_pallas_leaves_q8(
        bt, jnp.asarray(wch), jnp.asarray(chq), num_bins=B, pipeline="dma"))
    want = np.zeros((Q_LEAF_CHANNELS, f, B, 3), np.int32)
    rows = np.nonzero(chq >= 0)[0]
    for j in range(f):
        np.add.at(want[:, j], (chq[rows], bins[rows, j]), wch[:3, rows].T)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.asarray(
        build_histogram_pallas_leaves_q8(
            bt, jnp.asarray(wch), jnp.asarray(chq), num_bins=B,
            pipeline="blockspec")))

    ch = rng.randint(-1, LEAF_CHANNELS, n).astype(np.int32)
    g, h, m, chd = map(jnp.asarray, (grad, hess, mask, ch))
    w8 = pack_weights8(g, h, m)
    got = np.asarray(build_histogram_pallas_leaves(
        bt, w8, chd, num_bins=B, pipeline="dma"))
    np.testing.assert_array_equal(
        got, np.asarray(_parent_leaves_dma_bf16(bt, w8, chd)))
    ref = np.asarray(build_histogram_leaves(
        jnp.asarray(bins), g, h, m, chd, num_channels=LEAF_CHANNELS,
        num_bins=B, impl="segment"))
    assert np.abs(got - ref).max() / max(1.0, np.abs(ref).max()) < 1e-5
    np.testing.assert_array_equal(got[..., 2], ref[..., 2])


def test_leaves_kernels_bad_rows_raise():
    bins, grad, hess, mask = _data(B=16)
    bt = jnp.asarray(bins.T.copy())
    w8 = pack_weights8(*map(jnp.asarray, (grad, hess, mask)))
    ch = jnp.zeros((N,), jnp.int32)
    with pytest.raises(ValueError, match="pad_rows"):
        build_histogram_pallas_leaves(bt[:, :-8], w8[:, :-8], ch[:-8],
                                      num_bins=16)
    with pytest.raises(ValueError, match="wch"):
        build_histogram_pallas_leaves_q8(
            bt, jnp.zeros((8, N // 2), jnp.int8), ch.astype(jnp.int8),
            num_bins=16)


# -- row-update / trial-channel kernel ---------------------------------------

def _row_update_plain(cols, rl, tab):
    """The W splits applied one after the other in plain XLA (the
    reference ``chip_smoke.py`` holds the kernel to on the chip)."""
    from chip_smoke import _row_update_reference
    rl_new, ch = _row_update_reference(*map(jnp.asarray, (cols, rl, tab)))
    return np.asarray(rl_new), np.asarray(ch)


def _feature_ids(form, f, w, rng):
    if form == "shuffled":
        return rng.permutation(f)[np.arange(w) % f]
    if form == "repeated":
        return np.resize(rng.randint(0, f, 2), w)
    return rng.randint(-3, f + 4, w)            # clipped: ids off both ends


# row blocks of 4096 rows (one, and three) and of 16384 (two, each swept
# in four strips of lanes)
@pytest.mark.parametrize("n", [4096, 3 * 4096, 2 * 16384])
@pytest.mark.parametrize("inactive", [False, True])
@pytest.mark.parametrize("form,f", [
    ("cols", 6), ("shuffled", 67), ("repeated", 67), ("clipped", 67),
    ("shuffled", 8), ("repeated", 8), ("clipped", 8)])
def test_row_update_dma_bitwise_and_trial(form, f, inactive, n):
    """The ``dma`` row update, handed the W columns (``cols``) or the bin
    matrix and W feature ids it fetches by itself: ``rl`` and ``ch``
    bitwise equal to the splits applied one by one in numpy, to the
    ``blockspec`` kernel and (fetch forms) to the ``cols`` form on the
    gathered columns; the trial form leaves ``rl`` as it was."""
    rng = np.random.RandomState(3)
    W = 5
    bins = rng.randint(0, 16, (f, n)).astype(np.uint8)
    feats = np.arange(W) if form == "cols" else _feature_ids(form, f, W, rng)
    cols = bins[np.clip(feats, 0, f - 1)]
    rl = rng.randint(0, 3, n).astype(np.int32)
    act = (rng.rand(W) < 0.5) if inactive else np.ones(W, bool)
    tab = np.stack([
        rng.randint(0, 16, W), np.where(rng.rand(W) < 0.5, 15, -1),
        rng.randint(0, 2, W), rng.randint(0, 2, W), rng.randint(0, 3, W),
        np.arange(3, 3 + W), act, rng.randint(0, 99, W)]).astype(np.int32)
    want_rl, want_ch = _row_update_plain(cols, rl, tab)

    rl_d, tab_d = jnp.asarray(rl), jnp.asarray(tab)
    if form == "cols":
        kw, src = {}, jnp.asarray(cols)
    else:
        kw = {"feats": jnp.asarray(feats.astype(np.int32))}
        # as the grower passes it: the view made once, ahead of the calls
        src = bin_rows_view(jnp.asarray(bins), "dma")
        assert src.shape == (f, 8, n // 8)
    rd, cd = wave_row_update_pallas(src, rl_d, tab_d, pipeline="dma", **kw)
    np.testing.assert_array_equal(np.asarray(rd), want_rl)
    np.testing.assert_array_equal(np.asarray(cd), want_ch)
    # the same kernel from the columns, and the blockspec kernel (which
    # gathers the columns in front of itself)
    rc, cc = wave_row_update_pallas(jnp.asarray(cols), rl_d, tab_d,
                                    pipeline="dma")
    rb, cb = wave_row_update_pallas(
        jnp.asarray(cols if form == "cols" else bins), rl_d, tab_d,
        pipeline="blockspec", **kw)
    for got_rl, got_ch in ((rc, cc), (rb, cb)):
        np.testing.assert_array_equal(np.asarray(got_rl), np.asarray(rd))
        np.testing.assert_array_equal(np.asarray(got_ch), np.asarray(cd))
    # trial form commits nothing: new_right_id = the split leaf itself
    trial = tab.copy()
    trial[5] = trial[4]
    rt, _ = wave_row_update_pallas(src, rl_d, jnp.asarray(trial),
                                   pipeline="dma", **kw)
    np.testing.assert_array_equal(np.asarray(rt), rl)
    ch = wave_trial_channels_pallas(
        src, rl_d, tab_d[4], tab_d[0], tab_d[1], tab_d[2] > 0, tab_d[3],
        tab_d[6] > 0, pipeline="dma", **kw)
    np.testing.assert_array_equal(
        np.asarray(ch), _row_update_plain(cols, rl, trial)[1])


def test_row_update_sites_say_who_fetched():
    """``TrainRecord``'s kernel sites tell the two forms apart, with the
    bytes the kernel reads (W x N + 9 N) either way."""
    from lightgbm_tpu.telemetry.train_record import (hist_kernel_reset,
                                                     hist_kernel_snapshot)
    n, f, W = 4096, 8, 3
    bins = jnp.zeros((f, n), jnp.uint8)
    rl = jnp.zeros((n,), jnp.int32)
    tab = jnp.zeros((8, W), jnp.int32)
    hist_kernel_reset()
    wave_row_update_pallas(bins[:W], rl, tab, pipeline="dma")
    wave_row_update_pallas(bins, rl, tab, feats=jnp.arange(W),
                           pipeline="dma")
    wave_row_update_pallas(bins, rl, tab, feats=jnp.arange(W),
                           pipeline="blockspec")
    sites = hist_kernel_snapshot()
    want = {"count": 1, "bytes": W * n + 9 * n}
    assert sites["ops/hist_kernel/row_update/dma"] == want
    assert sites["ops/hist_kernel/row_update/dma/fetch"] == want
    assert sites["ops/hist_kernel/row_update/blockspec"] == want
    with pytest.raises(ValueError, match="feats="):
        wave_row_update_pallas(bins, rl, tab, pipeline="dma")


# -- vmap-to-grid batching rule (the multitrain unlock) ----------------------

@pytest.mark.parametrize("f,B", [(6, 16), (35, 255)])
def test_vmap_batching_bitwise(f, B):
    """jax's pallas_call batching rule lowers the model axis to a
    leading grid dimension; per-lane results must be bit-identical to
    the unbatched calls for BOTH pipelines (lifts the multitrain
    segment|onehot gate, ROADMAP item 4).  At F=35, B=255 the DMA
    kernel's ragged last tile finds its feature-tile index behind the
    batch dimension."""
    bins, _, _, mask = _data(B=B, f=f)
    rng = np.random.RandomState(4)
    M = 2
    wch = np.zeros((M, 8, N), np.int8)
    for k in range(M):
        wch[k, 0] = rng.randint(-50, 50, N)
        wch[k, 1] = rng.randint(0, 50, N)
        wch[k, 2] = 1
    ch = jnp.asarray(rng.randint(-1, Q_LEAF_CHANNELS, N).astype(np.int8))
    bt = jnp.asarray(bins.T.copy())
    wchb = jnp.asarray(wch)
    for pipe in ("blockspec", "dma"):
        def one(w_, pipe=pipe):
            return build_histogram_pallas_leaves_q8(bt, w_, ch,
                                                    num_bins=B,
                                                    pipeline=pipe)
        got = np.asarray(jax.jit(jax.vmap(one))(wchb))
        want = np.stack([np.asarray(one(wchb[k])) for k in range(M)])
        np.testing.assert_array_equal(got, want, err_msg=pipe)


# -- packed4 XLA scatter impl ------------------------------------------------

@pytest.mark.parametrize("f", [4, 5])
def test_packed4_xla_impl(f):
    bins, grad, hess, mask = _data(f=f, B=13)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask))
    ref = np.asarray(build_histogram(*args, num_bins=13, impl="segment"))
    got = np.asarray(build_histogram(*args, num_bins=13, impl="packed4"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="packed4"):
        build_histogram(*args, num_bins=64, impl="packed4")


# -- autotune: variant candidates + on-disk winner cache ---------------------

def test_autotune_disk_cache(tmp_path, monkeypatch):
    cache = tmp_path / "hist_autotune.json"
    monkeypatch.setenv("LGBM_TPU_AUTOTUNE_CACHE", str(cache))
    from lightgbm_tpu.learner import autotune
    X = np.random.RandomState(0).randint(0, 13, (N, 4)).astype(np.uint8)
    win = autotune.pick_hist_impl(X, 13, candidates=("segment", "packed4"),
                                  reps=2)
    assert win in ("segment", "packed4")
    assert cache.exists()
    # a fresh process (simulated: cleared in-memory caches) skips the
    # re-measurement pass and returns the persisted winner
    autotune._CACHE.clear()
    autotune._DISK_LOADED.clear()
    assert autotune.pick_hist_impl(
        X, 13, candidates=("segment", "packed4"), reps=2) == win


def test_autotune_default_candidates():
    from lightgbm_tpu.learner.autotune import default_candidates
    assert default_candidates("tpu", 255) == ("pallas", "pallas:blockspec",
                                              "onehot")
    assert "pallas:packed4" in default_candidates("tpu", 16)
    assert default_candidates("cpu", 16) == ("segment", "packed4")
    assert default_candidates("cpu", 255) == ("segment",)


def test_autotune_apply_winner():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner.autotune import apply_winner
    cfg = Config({})
    apply_winner(cfg, "pallas:blockspec")
    assert cfg.tpu_histogram_impl == "pallas"
    assert cfg.tpu_pallas_pipeline == "blockspec"
    assert cfg.tpu_hist_pack4 is False  # blockspec beat the packed DMA form
    # a PLAIN pallas winner beat the packed candidate: pack4 must clear,
    # else training would run the variant the probe just rejected
    apply_winner(cfg, "pallas")
    assert cfg.tpu_hist_pack4 is False
    assert cfg.tpu_pallas_pipeline == "dma"
    apply_winner(cfg, "pallas:packed4")
    assert cfg.tpu_hist_pack4 is True
    apply_winner(cfg, "segment")
    assert cfg.tpu_histogram_impl == "segment"


def test_pipeline_blockspec_disables_pack4():
    """Explicit tpu_pallas_pipeline=blockspec is the measured-dead-ends
    A/B knob: it must actually run the v1 layout, so pack4 (a DMA-only
    layout) turns off instead of silently forcing the pipeline back."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner.serial import SerialTreeLearner
    nb = np.full(4, 15, np.int32)
    flags = np.zeros(4, bool)
    mk = lambda pipe: SerialTreeLearner(
        Config({"num_leaves": 7, "tree_grow_mode": "wave",
                "tpu_histogram_impl": "pallas", "max_bin": 15,
                "tpu_pallas_pipeline": pipe, "verbosity": -1}),
        4, 15, nb, flags, flags)
    assert mk("auto").pack4 is True
    assert mk("dma").pack4 is True
    assert mk("blockspec").pack4 is False


# -- telemetry: the hist_kernel site -----------------------------------------

def test_hist_kernel_telemetry_site():
    from lightgbm_tpu.telemetry.train_record import (TrainRecord,
                                                     hist_kernel_snapshot)
    bins, grad, hess, mask = _data(B=16)
    rec = TrainRecord()
    build_histogram_pallas(jnp.asarray(bins.T.copy()), jnp.asarray(grad),
                           jnp.asarray(hess), jnp.asarray(mask),
                           num_bins=16, pipeline="dma")
    snap = rec.snapshot()
    sites = snap["hist_kernel"]
    assert any(k.startswith("ops/hist_kernel/single/dma") for k in sites)
    site = next(k for k in sites if k.startswith("ops/hist_kernel/single"))
    assert sites[site]["count"] >= 1
    assert sites[site]["bytes"] >= N * F  # at least the bin bytes
    assert hist_kernel_snapshot()  # process-wide tally holds it too
    assert "features" not in sites[site]  # only the leaf dma kernels say


@pytest.mark.parametrize("f,padded,contracted",
                         [(35, 64, 40), (67, 96, 72), (28, 32, 32)])
def test_hist_kernel_telemetry_contracted_features(f, padded, contracted):
    """The DMA leaf kernels say how many of their padded feature rows
    they contract, in the site's record and in the kernel's name (traced
    only: nothing runs)."""
    from lightgbm_tpu.telemetry.train_record import TrainRecord
    S = jax.ShapeDtypeStruct
    rec = TrainRecord()
    jax.eval_shape(
        lambda b, w, c: build_histogram_pallas_leaves_q8(
            b, w, c, num_bins=255, pipeline="dma"),
        S((f, N), jnp.uint8), S((8, N), jnp.int8), S((N,), jnp.int8))
    jax.eval_shape(
        lambda b, w, c: build_histogram_pallas_leaves(
            b, w, c, num_bins=255, pipeline="dma"),
        S((f, N), jnp.uint8), S((8, N), jnp.bfloat16), S((N,), jnp.int32))
    sites = rec.snapshot()["hist_kernel"]
    for site, kind, g in (("ops/hist_kernel/leaves_q8/dma", "_q8", 8),
                          ("ops/hist_kernel/leaves/dma", "", 4)):
        assert sites[site]["count"] == 1
        assert sites[site]["features"] == padded
        assert sites[site]["contracted_features"] == contracted
        assert (f"lgbm_hist_leaves{kind}_dma_f{padded}_fc{contracted}"
                f"_b256_g{g}_kr4096_n{N}") in traced_kernels()


# -- Dataset 4-bit packed storage --------------------------------------------

def test_dataset_device_bins_packed4():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(500, 4)
    ds = lgb.Dataset(X, rng.rand(500), params={"max_bin": 15,
                                               "verbosity": -1})
    ds.construct(None)
    pk = ds.device_bins_packed4()
    n_pad = pad_rows(500)
    assert pk.shape == (ds.num_feature(), n_pad // 2)
    assert pk.dtype == jnp.uint8
    got = np.asarray(unpack_bins4(pk))[:, :500]
    np.testing.assert_array_equal(got, ds.X_binned.T)
    assert ds.device_bins_packed4() is pk  # cached
    ds255 = lgb.Dataset(X, rng.rand(500), params={"verbosity": -1})
    ds255.construct(None)
    if int(np.max(ds255.num_bins_per_feature)) > 16:
        with pytest.raises(ValueError, match="max_bin"):
            ds255.device_bins_packed4()


# -- the grower hands the row update the bin matrix, not columns --------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters
    (pjit, while, cond branches; a pallas_call's kernel too)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("quantized", [True, False], ids=["q8", "exact"])
def test_grower_builds_no_columns_for_the_row_update(quantized):
    """Ramp, waves, endgame flush and trial channels all route rows
    through the fetching kernel: the jitted grower slices no row out of
    the bin matrix (or the ramp's subsample of it) and concatenates no
    (W, N) block, and grows the tree the ``blockspec`` pipeline (which
    gathers columns in front of its kernel) grows, bit for bit.  Exact
    arithmetic at wave size 1 is also the partitioned grower's tree."""
    from lightgbm_tpu.learner.wave import make_wave_grow_fn
    from lightgbm_tpu.ops.split import SplitParams
    f, b, n, w = 6, 64, 2 * 4096, 4
    rng = np.random.RandomState(0)
    bins = rng.randint(0, b - 1, (f, n)).astype(np.uint8)
    args = (jnp.asarray(bins), jnp.asarray(rng.randn(n).astype(np.float32)),
            jnp.full((n,), 0.25, jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.full((f,), b, jnp.int32), jnp.zeros((f,), bool),
            jnp.zeros((f,), bool), jnp.zeros((f,), jnp.int32),
            jnp.zeros((f,), jnp.float32), (), jnp.ones((f,), bool))
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)

    def grower(pipeline):
        return make_wave_grow_fn(
            num_leaves=13, num_features=f, max_bins=b, max_depth=0,
            split_params=sp, hist_impl="pallas", any_cat=False,
            interpret=True, jit=True, wave_size=w, stochastic=False,
            exact_endgame=True, spec_ramp=True, quantized=quantized,
            renew_leaf=quantized, pipeline=pipeline)

    grow = grower("dma")
    sliced, stacked, kernels = [], [], set()
    for eqn in _eqns(jax.make_jaxpr(grow)(*args).jaxpr):
        name = eqn.primitive.name
        if name in ("dynamic_slice", "gather") and \
                eqn.invars[0].aval.shape[0] == f and \
                eqn.invars[0].aval.dtype == jnp.uint8:
            sliced.append(eqn)
        if name == "concatenate" and eqn.outvars[0].aval.shape == (w, n):
            stacked.append(eqn)
        if name == "pallas_call":
            kernels.add(eqn.params["name"])
    assert not sliced, sliced
    assert not stacked, stacked
    assert f"lgbm_wave_row_update_dma_w{w}_f{f}_kr{n}_n{n}" in kernels

    got, want = grow(*args), grower("blockspec")(*args)
    assert int(got.num_leaves) == 13 and int(got.endgame_passes) > 0 \
        and int(got.ramp_committed) > 0
    for name, a, c in zip(got._fields, got, want):
        if name == "hist_rows_contracted":
            # [count, unit]: the dma pipeline compacts the wave and
            # endgame passes' rows, blockspec loops over all of them
            assert int(a[0, 0]) <= int(c[0, 0]) == \
                int(want.hist_passes) * (n // int(c[0, 1]))
            continue
        if name == "pass_log":
            # the same passes (kind, leaves, active lanes); what they
            # looped over and in which blocks is the pipeline's
            np.testing.assert_array_equal(np.asarray(a)[..., (0, 1, 3)],
                                          np.asarray(c)[..., (0, 1, 3)])
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=name)

    if not quantized:
        import lightgbm_tpu as lgb
        X = rng.randn(4000, 8).astype(np.float32)
        X[rng.rand(4000, 8) < 0.05] = np.nan
        y = ((np.nan_to_num(X) @ rng.randn(8) + 0.5 * rng.randn(4000)) > 0
             ).astype(np.float64)
        p = {"objective": "binary", "num_leaves": 31, "max_bin": 63,
             "learning_rate": 0.2, "verbosity": -1, "min_data_in_leaf": 20}
        part = lgb.train(dict(p, tree_grow_mode="partition"),
                         lgb.Dataset(X, y), num_boost_round=4).predict(X)
        wave = lgb.train(dict(p, tree_grow_mode="wave", tpu_wave_size=1,
                              tpu_histogram_impl="pallas",
                              tpu_pallas_pipeline="dma"),
                         lgb.Dataset(X, y), num_boost_round=4).predict(X)
        np.testing.assert_allclose(wave, part, atol=2e-4)
