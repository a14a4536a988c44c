"""Exact int32 histogram sums at any skew (PR 34, ops/quantize.py): where
the rows of one (leaf, bin) times the largest level can pass 2^31 - 1 the q8
kernels add a pass up in segments and the grower carries the sums as two
limbs.  The bound, the limbs at the smallest size that shows a wrap, the
segmented kernels against the one-accumulation ones, and a grower that
segments against one that does not: the same trees."""

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import quantize as qz
from lightgbm_tpu.ops.histogram_pallas import build_histogram_pallas_leaves_q8


@pytest.mark.parametrize("rows, share, want", [
    # the four accepted cells: 83K rows a bin of 21.25M (1/255: 0.4%)
    (21_250_048, 1 / 255, 0),
    # the raw click log: a column 77% missing, 45.84M rows -> 2^31 / 127 in blocks
    (45_842_432, 0.77, 16_908_288),
    (45_842_432, 0.05, 0),              # 4 x 5% x 45.8M x 127 stays under 2^31
    (45_842_432, 0.10, 16_908_288),     # 4 x 10% does not
    (21_250_048, 1.0, 16_908_288),      # a mapper of unknown origin: share 1
    (16_900_000, 1.0, 0),               # no leaf can hold more rows than there are
    (6000, 1.0, 0),
])
def test_the_bound(rows, share, want):
    assert qz.hist_acc_rows(rows, 127, 127, share) == want
    assert qz.INT32_ACC_MAX // 127 == 16_909_320


def test_fewer_levels_move_the_bound():
    assert qz.hist_acc_rows(45_842_432, 7, 14, 0.77) == 0     # int4: 14 x 35M < 2^31
    assert qz.hist_acc_rows(200_000_000, 7, 14, 1.0) == 153_391_104


def test_limbs_hold_what_an_int32_cannot():
    """Three exact partial sums of 2^31 - 1, -(2^31 - 1) and small ones: their
    sum wraps an int32 and is exact as limbs."""
    big = np.int32(2**31 - 1)
    parts = [jnp.asarray([[big, big, 1000], [-big, 5, 7]], jnp.int32),
             jnp.asarray([[big, 3, 2000], [-big, 6, 8]], jnp.int32),
             jnp.asarray([[5, big, 3000], [-9, 7, 9]], jnp.int32)]
    want = sum(np.asarray(p, np.int64) for p in parts)
    assert (np.asarray(sum(parts)) != want).any()             # the narrow sum wraps
    limbs = np.asarray(qz.hist_limbs(parts)).astype(np.int64)
    assert limbs.shape == (2, 5)
    np.testing.assert_array_equal(limbs[:, 3:] * 65536 + limbs[:, :2], want[:, :2])
    np.testing.assert_array_equal(limbs[:, 2], want[:, 2])
    np.testing.assert_allclose(np.asarray(qz.dequant_limbs(jnp.asarray(limbs, jnp.int32))),
                               want.astype(np.float32), rtol=1e-7)
    # limbs subtract like the sums: parent - child
    child = qz.hist_limbs(parts[:1])
    rest = np.asarray(qz.hist_limbs(parts) - child).astype(np.int64)
    np.testing.assert_array_equal(rest[:, 3:] * 65536 + rest[:, :2],
                                  (want - np.asarray(parts[0], np.int64))[:, :2])


@pytest.mark.parametrize("pipeline, compact", [("dma", False), ("dma", True),
                                                ("blockspec", False)])
def test_segmented_kernels_sum_to_the_whole_pass(pipeline, compact):
    rng = np.random.RandomState(0)
    n, f, B = 4096 * 5, 7, 255
    bins = jnp.asarray(rng.randint(0, B, (f, n)).astype(np.uint8))
    wch = np.zeros((8, n), np.int8)
    wch[0], wch[1], wch[2] = rng.randint(-127, 128, n), rng.randint(0, 128, n), 1
    ch = jnp.asarray(np.where(rng.rand(n) < 0.4, rng.randint(0, 42, n), -1).astype(np.int8))
    ref = np.asarray(build_histogram_pallas_leaves_q8(
        bins, jnp.asarray(wch), ch, num_bins=B, pipeline=pipeline))
    out = build_histogram_pallas_leaves_q8(bins, jnp.asarray(wch), ch, num_bins=B,
                                           pipeline=pipeline, compact=compact, acc_rows=8192)
    if compact:
        out, rows = out
        assert int(rows[0]) == 12288            # 8,200 active rows: the third segment runs
    out = np.asarray(out).astype(np.int64)
    assert out.shape == (42, f, B, 5)
    np.testing.assert_array_equal(out[..., 3:] * 65536 + out[..., :2], ref[..., :2])
    np.testing.assert_array_equal(out[..., 2], ref[..., 2])


def _train(monkeypatch, acc_max, extra, cats=()):
    rng = np.random.RandomState(4)
    n = 20000
    X = rng.randn(n, 6)
    X[rng.rand(n, 6) < [0.05, 0.05, 0.3, 0.05, 0.05, 0.0]] = np.nan   # one full bin
    X[:, 5] = (rng.zipf(1.4, n) - 1) % 12 if cats else X[:, 5]
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 1]) ** 2 + 0.3 * (X[:, 5] % 3)
         + 0.5 * rng.randn(n) > 0.8).astype(float)
    if acc_max:
        monkeypatch.setattr(qz, "INT32_ACC_MAX", acc_max)
    p = dict({"objective": "binary", "num_leaves": 15, "max_bin": 63, "min_data_in_leaf": 5,
              "tree_grow_mode": "wave", "tpu_histogram_impl": "pallas", "verbosity": -1,
              "use_quantized_grad": True, "num_grad_quant_bins": 254,
              "quant_train_renew_leaf": True, "categorical_feature": list(cats)}, **extra)
    bst = lgb.train(p, lgb.Dataset(X, y, categorical_feature=list(cats)), 3)
    return bst, bst._gbdt.train_record.snapshot()


@pytest.mark.parametrize("kind, extra, cats", [
    ("ramp-endgame", {"tpu_wave_size": 4}, ()),      # verify pass, endgame bank
    ("root-waves", {}, ()),
    ("categorical", {}, (5,)),
])
def test_a_grower_that_segments_grows_the_same_trees(monkeypatch, kind, extra, cats):
    """127 x 8192 as the accumulator's range: 20,480 padded rows are summed
    8,192 at a time and every integer histogram carries limbs; sums, splits
    and leaf values are those of the narrow grower, to the last digit."""
    narrow, snap_n = _train(monkeypatch, 0, extra, cats)
    wide, snap_w = _train(monkeypatch, 127 * 8192 + 100, extra, cats)
    assert snap_n["grower"]["hist_acc_rows"] == 0
    assert snap_w["grower"]["hist_acc_rows"] == 8192
    assert snap_w["grower"]["ramp"] == snap_n["grower"]["ramp"] == (kind == "ramp-endgame")
    assert wide.model_to_string() == narrow.model_to_string()
    assert [t["hist_passes"] for t in snap_w["trees"]] == \
        [t["hist_passes"] for t in snap_n["trees"]]


def test_a_learner_that_cannot_segment_warns(monkeypatch):
    from lightgbm_tpu.utils import log as lg
    seen = []
    monkeypatch.setattr(lg, "log_warning", lambda m: seen.append(m))
    from lightgbm_tpu.models import gbdt
    monkeypatch.setattr(gbdt, "log_warning", lambda m: seen.append(m))
    monkeypatch.setattr(qz, "INT32_ACC_MAX", 127)
    rng = np.random.RandomState(0)
    X = rng.randn(500, 3)
    y = (X[:, 0] > 0).astype(float)
    p = {"objective": "binary", "num_leaves": 4, "verbosity": -1, "use_quantized_grad": True,
         "num_grad_quant_bins": 254, "tree_grow_mode": "partition"}
    lgb.train(p, lgb.Dataset(X, y), 1)
    assert any("does not segment" in m for m in seen)
