"""Histogram + split-finding op tests against numpy references
(the kernels replacing dense_bin.hpp ConstructHistogram and
feature_histogram.hpp FindBestThresholdSequentially)."""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.histogram import build_histogram, histogram_subtract
from lightgbm_tpu.ops.split import (NEG_INF, SplitParams,
                                    best_split_per_feature, leaf_output)


def _np_histogram(bins, grad, hess, mask, B):
    n, f = bins.shape
    out = np.zeros((f, B, 3))
    for i in range(n):
        if mask[i] == 0:
            continue
        for j in range(f):
            b = bins[i, j]
            out[j, b, 0] += grad[i]
            out[j, b, 1] += hess[i]
            out[j, b, 2] += 1.0
    return out


@pytest.mark.parametrize("impl", ["segment", "onehot"])
def test_histogram_matches_numpy(impl):
    rng = np.random.RandomState(0)
    n, f, B = 500, 4, 16
    bins = rng.randint(0, B, (n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32)
    mask = (rng.rand(n) > 0.3).astype(np.float32)
    got = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(grad),
                                     jnp.asarray(hess), jnp.asarray(mask),
                                     num_bins=B, impl=impl))
    want = _np_histogram(bins, grad, hess, mask, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["segment", "onehot"])
def test_histogram_chunked(impl):
    rng = np.random.RandomState(1)
    n, f, B = 1000, 3, 8
    bins = rng.randint(0, B, (n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = np.ones(n, np.float32)
    mask = np.ones(n, np.float32)
    full = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(grad),
                                      jnp.asarray(hess), jnp.asarray(mask),
                                      num_bins=B, impl=impl))
    chunked = np.asarray(build_histogram(jnp.asarray(bins), jnp.asarray(grad),
                                         jnp.asarray(hess), jnp.asarray(mask),
                                         num_bins=B, impl=impl,
                                         rows_per_chunk=96))
    np.testing.assert_allclose(full, chunked, rtol=1e-4, atol=1e-4)


def test_histogram_subtraction():
    rng = np.random.RandomState(2)
    n, f, B = 300, 3, 8
    bins = rng.randint(0, B, (n, f)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = np.ones(n, np.float32)
    left = (rng.rand(n) > 0.5).astype(np.float32)
    all_mask = np.ones(n, np.float32)
    h_all = build_histogram(jnp.asarray(bins), jnp.asarray(grad),
                            jnp.asarray(hess), jnp.asarray(all_mask), num_bins=B)
    h_left = build_histogram(jnp.asarray(bins), jnp.asarray(grad),
                             jnp.asarray(hess), jnp.asarray(left), num_bins=B)
    h_right = build_histogram(jnp.asarray(bins), jnp.asarray(grad),
                              jnp.asarray(hess), jnp.asarray(1 - left),
                              num_bins=B)
    np.testing.assert_allclose(np.asarray(histogram_subtract(h_all, h_left)),
                               np.asarray(h_right), rtol=1e-3, atol=1e-3)


def _np_best_split(hist, parent, l1, l2, min_cnt, min_hess):
    """Brute-force split scan for one numerical feature, missing->right."""
    def thr_l1(g):
        return np.sign(g) * max(abs(g) - l1, 0.0)

    def gain(g, h):
        return thr_l1(g) ** 2 / (h + l2) if h + l2 > 0 else 0.0

    B = hist.shape[0]
    pg = gain(parent[0], parent[1])
    best = (-np.inf, -1)
    for b in range(B - 1):
        gl = hist[: b + 1, 0].sum()
        hl = hist[: b + 1, 1].sum()
        cl = hist[: b + 1, 2].sum()
        gr, hr, cr = parent[0] - gl, parent[1] - hl, parent[2] - cl
        if cl < min_cnt or cr < min_cnt or hl < min_hess or hr < min_hess:
            continue
        g = gain(gl, hl) + gain(gr, hr) - pg
        if g > best[0]:
            best = (g, b)
    return best


def test_split_matches_bruteforce():
    rng = np.random.RandomState(3)
    B, F = 12, 3
    hist = rng.randn(F, B, 3).astype(np.float32)
    hist[..., 1] = np.abs(hist[..., 1]) + 0.1   # positive hessians
    hist[..., 2] = rng.randint(5, 50, (F, B))   # counts
    parent = hist.sum(axis=1)[0]  # use feature 0's totals for all (same data)
    hist = np.broadcast_to(hist[0], (F, B, 3)).copy()
    params = SplitParams(lambda_l1=0.1, lambda_l2=0.5, min_data_in_leaf=10,
                         min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0)
    fs = best_split_per_feature(
        jnp.asarray(hist), jnp.asarray(parent),
        jnp.full(F, B, jnp.int32), jnp.zeros(F, jnp.bool_),
        jnp.zeros(F, jnp.bool_), params)
    want_gain, want_bin = _np_best_split(hist[0], parent, 0.1, 0.5, 10, 1e-3)
    np.testing.assert_allclose(float(fs.gain[0]), want_gain, rtol=1e-4)
    assert int(fs.threshold_bin[0]) == want_bin


def test_split_min_data_constraint():
    B = 8
    hist = np.zeros((1, B, 3), np.float32)
    hist[0, :, 0] = np.linspace(-1, 1, B)
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 3.0  # 3 per bin, 24 total
    parent = hist[0].sum(axis=0)
    params = SplitParams(min_data_in_leaf=20, min_sum_hessian_in_leaf=0.0)
    fs = best_split_per_feature(
        jnp.asarray(hist), jnp.asarray(parent), jnp.asarray([B], jnp.int32),
        jnp.zeros(1, jnp.bool_), jnp.zeros(1, jnp.bool_), params)
    # no split leaves >=20 on both sides of 24 rows
    assert float(fs.gain[0]) <= NEG_INF / 2


def test_split_nan_direction():
    B = 8
    # feature with NaN bin at index B-1 holding strong negative gradients
    hist = np.zeros((1, B, 3), np.float32)
    hist[0, :4, 0] = 1.0
    hist[0, 4:7, 0] = -1.0
    hist[0, B - 1, 0] = -5.0
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 10.0
    parent = hist[0].sum(axis=0)
    params = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0)
    fs = best_split_per_feature(
        jnp.asarray(hist), jnp.asarray(parent), jnp.asarray([B], jnp.int32),
        jnp.zeros(1, jnp.bool_), jnp.asarray([True]), params)
    assert float(fs.gain[0]) > 0
    # NaN joins the negative side: either missing-right with negatives right,
    # or missing-left grouping NaN with negatives; sums must be consistent
    total = parent
    ls = np.asarray(fs.left_sum[0])
    rs = np.asarray(fs.right_sum[0])
    np.testing.assert_allclose(ls + rs, total, rtol=1e-5)


def test_categorical_split():
    B = 6
    # category 2 is strongly negative -> best one-vs-rest split
    hist = np.zeros((1, B, 3), np.float32)
    hist[0, :, 0] = np.array([0.5, 0.2, -4.0, 0.1, 0.3, 0.0])
    hist[0, :, 1] = 1.0
    hist[0, :, 2] = 20.0
    parent = hist[0].sum(axis=0)
    params = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0,
                         cat_l2=0.0)
    fs = best_split_per_feature(
        jnp.asarray(hist), jnp.asarray(parent), jnp.asarray([B], jnp.int32),
        jnp.asarray([True]), jnp.zeros(1, jnp.bool_), params)
    assert int(fs.threshold_bin[0]) == 2
    assert float(fs.gain[0]) > 0


def test_leaf_output():
    params = SplitParams(lambda_l1=0.0, lambda_l2=1.0)
    out = leaf_output(jnp.asarray(4.0), jnp.asarray(3.0), params)
    np.testing.assert_allclose(float(out), -1.0)
    params2 = SplitParams(lambda_l1=1.0, lambda_l2=0.0, max_delta_step=0.5)
    out2 = leaf_output(jnp.asarray(4.0), jnp.asarray(3.0), params2)
    np.testing.assert_allclose(float(out2), -0.5)  # clipped


def test_hist_impl_autotune_times_both(monkeypatch):
    """ShareStates-style one-shot timing on real shapes
    (learner/autotune.py; dataset.cpp:659-670 analog)."""
    import numpy as np
    monkeypatch.setenv("LGBM_TPU_AUTOTUNE_CACHE", "")  # no disk writes
    import jax
    from lightgbm_tpu.learner.autotune import _CACHE, pick_hist_impl
    rng = np.random.RandomState(0)
    X = rng.randint(0, 63, (2000, 5)).astype(np.uint8)
    win = pick_hist_impl(X, 63, candidates=("onehot", "segment"))
    assert win in ("onehot", "segment")
    # winners are keyed by device kind: a timing belongs to one chip
    assert (jax.devices()[0].device_kind, 2000, 5, 63,
            ("onehot", "segment")) in _CACHE
    # cached second call returns instantly with the same answer
    assert pick_hist_impl(X, 63, candidates=("onehot", "segment")) == win
