"""Row compaction in front of the DMA leaf-histogram kernels (ISSUE 31):
the wave and endgame passes contract only the rows of the leaves they
build.  Everything here runs the Pallas kernels interpreted, on the CPU:
results and counts, never a speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import serial
from lightgbm_tpu.ops import histogram_pallas as hp

KR = hp.DEFAULT_ROW_BLOCK


def _numpy_compact(bins, w, ch, kb):
    """Block by block: the active lanes in their order, then padding up
    to the next multiple of 128 (bins and weights 0, ch -1)."""
    out = ([], [], [])
    for lo in range(0, ch.shape[0], kb):
        act = ch[lo:lo + kb] >= 0
        c = int(act.sum())
        pad = -c % 128
        for dst, src, fill in zip(out, (bins, w, ch[None]), (0, 0, -1)):
            blk = src[:, lo:lo + kb][:, act]
            dst.append(np.pad(blk, ((0, 0), (0, pad)), constant_values=fill))
    return [np.concatenate(o, axis=1) for o in out]


def _channels(rng, n, share):
    if share == "one":
        ch = np.full(n, -1, np.int32)
        ch[n // 3] = 5
        return ch
    if share == "ragged":         # the last block holds a single lane
        ch = np.where(rng.rand(n) < 0.4, rng.randint(0, 42, n), -1)
        ch[-hp._CP_KB:] = -1
        ch[-7] = 41
        return ch.astype(np.int32)
    return np.where(rng.rand(n) < share, rng.randint(0, 42, n),
                    -1).astype(np.int32)


@pytest.mark.parametrize("share", [1.0, 0.0, "one", "ragged", 0.5, 0.1, 0.02])
@pytest.mark.parametrize("wdt", ["int8", "bfloat16"])
def test_compaction_kernel_against_numpy(share, wdt):
    rng = np.random.RandomState(3)
    n, f_pad, fc = 3 * hp._CP_KB, 16, 16
    bins = rng.randint(0, 256, (f_pad, n)).astype(np.uint8)
    if wdt == "int8":
        w = rng.randint(-127, 128, (8, n)).astype(np.int8)
    else:
        w = np.array(jnp.asarray(rng.randn(8, n), jnp.bfloat16))
    w[5:] = 0                     # the rows the packers leave zero
    ch = _channels(rng, n, share)
    b, ww, cc, steps, counts = (np.asarray(o) for o in hp._compact_rows_dma(
        jnp.asarray(bins), jnp.asarray(w), jnp.asarray(ch)[None], fc=fc,
        kr=KR, interpret=True))
    kb = hp._compact_block(n, f_pad)
    rb, rw, rc = _numpy_compact(bins, w, ch, kb)
    total = rc.shape[1]
    assert total % 128 == 0 and total == _numpy_total(ch, kb)
    # the plan's counts: active lanes, blocks, blocks with an active lane
    held = sum(bool((ch[lo:lo + kb] >= 0).any()) for lo in range(0, n, kb))
    assert counts.tolist() == [int((ch >= 0).sum()), n // kb, held]
    np.testing.assert_array_equal(b[:fc, :total], rb[:fc])
    np.testing.assert_array_equal(ww[:, :total].astype(np.float32),
                                  rw.astype(np.float32))
    np.testing.assert_array_equal(cc[:, :total], rc)
    # the leaf kernel's loop ends in padding: no channel, zero weights
    looped = int(steps[0]) * KR
    assert looped == max(KR, -(-total // KR) * KR) <= b.shape[1]
    assert (cc[:, total:looped] == -1).all()
    assert (ww[:, total:looped].astype(np.float32) == 0).all()


def _numpy_total(ch, kb):
    return sum(-(-int((ch[lo:lo + kb] >= 0).sum()) // 128) * 128
               for lo in range(0, ch.shape[0], kb))


def test_plan_kernel_with_a_ragged_last_step():
    """More sub-blocks than one step of the plan kernel takes, and not a
    whole number of steps: every lane's place in its window all the same."""
    rng = np.random.RandomState(8)
    sub, kb = hp._CP_SUB, 4096
    n = sub * (hp._CP_PLAN_ROWS + 8)
    ch = _channels(rng, n, 0.3)
    code, wt, off, steps, _ = (np.asarray(o) for o in hp._compact_plan(
        jnp.asarray(ch), kb=kb, kr=KR, interpret=True))
    act = (ch >= 0).reshape(n // kb, kb)
    before = np.cumsum(act, axis=1) - act             # in the block
    start = before.reshape(-1, sub)[:, 0]              # of each sub-block
    dest = (before.reshape(-1, sub) - start[:, None]) + start[:, None] % 128
    want = np.where(act.reshape(-1, sub),
                    (dest << hp._CP_CH_BITS) | (ch.reshape(-1, sub) + 1), -1)
    np.testing.assert_array_equal(code.reshape(-1, sub), want)
    np.testing.assert_array_equal(wt[:-1], start // 128)
    assert off[-1] == _numpy_total(ch, kb) and steps[0] == -(-off[-1] // KR)


def test_compaction_kernel_moves_wide_bins_in_row_groups():
    """More contracted rows than one selection matmul holds, and a block
    cut down to fit them in VMEM."""
    rng = np.random.RandomState(4)
    n, f_pad, fc = 3 * KR, 224, 200
    bins = rng.randint(0, 256, (f_pad, n)).astype(np.uint8)
    w = rng.randint(-127, 128, (8, n)).astype(np.int8)
    w[3:] = 0
    ch = _channels(rng, n, 0.3)
    kb = hp._compact_block(n, f_pad)
    assert kb < hp._CP_KB
    b, ww, cc, *_ = (np.asarray(o) for o in hp._compact_rows_dma(
        jnp.asarray(bins), jnp.asarray(w), jnp.asarray(ch)[None], fc=fc,
        kr=KR, interpret=True))
    rb, rw, rc = _numpy_compact(bins, w, ch, kb)
    total = rc.shape[1]
    np.testing.assert_array_equal(b[:fc, :total], rb[:fc])
    np.testing.assert_array_equal(ww[:, :total], rw)
    np.testing.assert_array_equal(cc[:, :total], rc)


@pytest.mark.parametrize("share", [1.0, 0.3, 0.05, 0.0])
@pytest.mark.parametrize("kind", ["q8", "bf16"])
def test_compacted_leaf_pass_equals_dense(kind, share):
    rng = np.random.RandomState(5)
    n, f = 6 * KR, 11
    bins = jnp.asarray(rng.randint(0, 255, (f, n)).astype(np.uint8))
    if kind == "q8":
        build, nch = hp.build_histogram_pallas_leaves_q8, hp.Q_LEAF_CHANNELS
        w = jnp.asarray(rng.randint(-127, 128, (8, n)).astype(np.int8)
                        ).at[3:].set(0)
    else:
        build, nch = hp.build_histogram_pallas_leaves, hp.LEAF_CHANNELS
        w = hp.pack_weights8(jnp.asarray(rng.randn(n), jnp.float32),
                             jnp.asarray(rng.rand(n), jnp.float32),
                             jnp.ones((n,), jnp.float32))
    ch = jnp.asarray(np.where(rng.rand(n) < share, rng.randint(0, nch, n),
                              -1).astype(np.int8))
    kw = dict(num_bins=255, pipeline="dma", interpret=True)
    dense = build(bins, w, ch, **kw)
    got, counts = build(bins, w, ch, compact=True, **kw)
    rows, active, blocks, held = (int(c) for c in counts)
    assert rows <= n and rows % KR == 0
    if share < 1.0:
        assert rows < n
    assert active == int((np.asarray(ch) >= 0).sum()) <= max(rows, KR)
    assert blocks == n // hp._compact_block(n, 16)
    assert held == (blocks if share > 0 else 0)
    if kind == "q8":
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))
    else:
        # the same products, summed in other row blocks: f32 sums of
        # hi/lo halves may part in their last bits, never more
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   rtol=2e-6, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[..., 2]),
                                      np.asarray(dense[..., 2]))  # counts


def test_dense_pipelines_report_every_row():
    """``blockspec`` and nibble-packed bins keep the dense form under
    ``compact``: the same histogram, and N rows looped."""
    rng = np.random.RandomState(6)
    n, f = 2 * KR, 5
    bins = jnp.asarray(rng.randint(0, 15, (f, n)).astype(np.uint8))
    w = jnp.asarray(rng.randint(-127, 128, (8, n)).astype(np.int8)
                    ).at[3:].set(0)
    ch = jnp.asarray(np.where(rng.rand(n) < 0.2, rng.randint(0, 42, n),
                              -1).astype(np.int8))
    want = hp.build_histogram_pallas_leaves_q8(
        bins, w, ch, num_bins=15, pipeline="blockspec", interpret=True)
    for kw in (dict(pipeline="blockspec"),
               dict(pipeline="dma", bins_packed=True)):
        b = hp.pack_bins4(bins) if kw.get("bins_packed") else bins
        got, counts = hp.build_histogram_pallas_leaves_q8(
            b, w, ch, num_bins=15, interpret=True, compact=True, **kw)
        assert counts.tolist() == [n, int((np.asarray(ch) >= 0).sum()), 1, 1]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the grower: one path per call site, chosen by what the site is
# ---------------------------------------------------------------------------

PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "min_data_in_leaf": 5, "tree_grow_mode": "wave",
          "tpu_histogram_impl": "pallas", "verbosity": -1}
Q8 = {"use_quantized_grad": True, "num_grad_quant_bins": 254,
      "quant_train_renew_leaf": True}


ROWS = {"serial": 9000, "data": 70000}   # three row blocks a shard


def _data(n):
    rng = np.random.RandomState(11)
    X = rng.randn(n, 6)
    y = (X[:, 0] + X[:, 1] ** 2 + 0.3 * rng.randn(n) > 0.8).astype(float)
    return X, y


@pytest.fixture
def dense_call_sites(monkeypatch):
    """The wave and endgame call sites forced onto the direct call: the
    builders drop ``compact`` and say they looped over every row."""
    for name in ("build_histogram_pallas_leaves",
                 "build_histogram_pallas_leaves_q8"):
        def dense(bins_t, w, ch, *, compact=False, _build=getattr(hp, name),
                  **kw):
            hist = _build(bins_t, w, ch, **kw)
            return (hist, hp.dense_pass_counts(w.shape[1], ch)) \
                if compact else hist
        monkeypatch.setattr(hp, name, dense)


def _train(extra, trees=2):
    X, y = _data(ROWS[extra["tree_learner"]])
    bst = lgb.train({**PARAMS, **extra}, lgb.Dataset(X, y), trees)
    rec = bst._gbdt.train_record.snapshot()
    text = "\n".join(ln for ln in bst.model_to_string().splitlines()
                     if not ln.startswith("[tpu_"))
    return text, rec


@pytest.mark.parametrize("learner", ["serial", "data"])
@pytest.mark.parametrize("kind", ["q8", "exact"])
def test_tree_text_equal_with_and_without_compaction(
        kind, learner, dma_everywhere, request):
    extra = {**(Q8 if kind == "q8" else {}), "tree_learner": learner}
    text, rec = _train(extra)
    sites = rec["hist_kernel"]
    assert any(s.startswith("ops/hist_kernel/leaves") and s.endswith("/dma")
               for s in sites), sorted(sites)
    assert "lgbm_hist_compact_dma" in " ".join(hp.traced_kernels())
    for t in rec["trees"]:
        assert t["hist_passes"] > 1
        assert 0 < t["hist_rows_contracted"] < t["hist_passes"] * _pass_rows(
            rec, learner)
    request.getfixturevalue("dense_call_sites")
    serial._GROW_FN_CACHE.clear()
    text_d, rec_d = _train(extra)
    assert text_d == text
    assert [t["hist_passes"] for t in rec_d["trees"]] == \
        [t["hist_passes"] for t in rec["trees"]]
    for t in rec_d["trees"]:
        assert t["hist_rows_contracted"] == \
            t["hist_passes"] * _pass_rows(rec_d, learner)


def _pass_rows(rec, learner):
    """Rows of one dense pass as the kernels see them, over all shards."""
    chips = rec["mesh"]["chips"] if learner == "data" else 1
    return chips * hp.pad_rows(-(-ROWS[learner] // chips))
