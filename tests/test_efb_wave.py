"""Exclusive Feature Bundling on the wave grower's normal path: bundled
columns keep uint8 codes and are routed by the fused row-update kernel (a slot
whose split feature lives in a bundle hands it the set of bundle codes that go
left), and the split scan reads member features out of the bundle histograms
by static slices (efb.make_scan_expand) where it used to gather (F, B) a leaf.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import efb
from lightgbm_tpu.ops.split import (SplitParams, best_split_per_feature,
                                    best_split_two_bin)


class _Mapper:
    def __init__(self, num_bin, default_bin=0):
        self.num_bin, self.default_bin = num_bin, default_bin


def _layout():
    """9 features: two singletons (7 and 12 bins), a bundle of four two-bin
    members (defaults 0, 0, 1, 0) and one four-bin member between them, a
    bundle of a three-bin and a five-bin member (defaults 1 and 4)."""
    mappers = [_Mapper(7), _Mapper(2), _Mapper(2), _Mapper(4, 2), _Mapper(2, 1), _Mapper(2),
               _Mapper(12, 3), _Mapper(3, 1), _Mapper(5, 4)]
    bundles = [[1, 2, 3, 4, 5], [0], [7, 8], [6]]
    return mappers, efb.build_bundle_info(mappers, bundles, 12)


def _arrays(info):
    return (jnp.asarray(info.exp_map), jnp.asarray(info.f_bundle), jnp.asarray(info.f_offset),
            jnp.asarray(info.f_default), jnp.asarray(info.f_nbins), jnp.asarray(info.f_single))


def test_the_sliced_scan_input_is_the_gathers_bit_for_bit():
    _, info = _layout()
    G, Bb, B, F = info.n_bundles, info.bundle_bins, 12, 9
    gather = jax.jit(efb.make_expand_hist(_arrays(info), F, G, Bb))
    se = efb.make_scan_expand(info.layout(), G, Bb, B)
    assert list(se.wide_ids) == [0, 3, 6, 7, 8]
    assert sorted(se.narrow_ids) == [1, 2, 4, 5]
    sliced = jax.jit(se.expand)
    rng = np.random.default_rng(0)
    for _ in range(5):
        hb = jnp.asarray(rng.standard_normal((G, Bb, 3)).astype(np.float32) * 100)
        total = jnp.asarray(rng.standard_normal(3).astype(np.float32) * 1000)
        want = np.asarray(gather(hb, total))
        wide, narrow = sliced(hb, total)
        assert np.array_equal(np.asarray(wide), want[se.wide_ids])
        # narrow[b, c, i]: channel c of bin b of feature narrow_ids[i]
        assert np.array_equal(np.moveaxis(np.asarray(narrow), 2, 0),
                              want[se.narrow_ids][:, :2])
        assert not want[se.narrow_ids][:, 2:].any()
    # no gather in the lowered program, where the oracle has one
    text = jax.jit(se.expand).lower(hb, total).as_text()
    assert "gather" not in text
    assert "gather" in jax.jit(efb.make_expand_hist(_arrays(info), F, G, Bb)).lower(
        hb, total).as_text()


def test_a_class_that_is_empty_is_left_out():
    mappers = [_Mapper(2), _Mapper(2), _Mapper(2)]
    info = efb.build_bundle_info(mappers, [[0, 1, 2]], 2)
    se = efb.make_scan_expand(info.layout(), 1, info.bundle_bins, 2)
    wide, narrow = se.expand(jnp.ones((1, info.bundle_bins, 3)), jnp.full((3,), 10.0))
    assert wide is None and narrow.shape == (2, 3, 3) and len(se.wide_ids) == 0
    info = efb.build_bundle_info([_Mapper(5), _Mapper(3)], [[0], [1]], 5)
    se = efb.make_scan_expand(info.layout(), 2, 5, 5)
    wide, narrow = se.expand(jnp.ones((2, 5, 3)), jnp.full((3,), 10.0))
    assert narrow is None and wide.shape == (2, 5, 3)


@pytest.mark.parametrize("params", [
    SplitParams(min_data_in_leaf=0, min_sum_hessian_in_leaf=3.0, any_cat=False),
    SplitParams(min_data_in_leaf=2, lambda_l1=0.5, lambda_l2=1.0, any_cat=False),
    SplitParams(min_data_in_leaf=1, path_smooth=2.0, max_delta_step=0.3, any_cat=False),
    SplitParams(min_data_in_leaf=1, use_monotone=True, monotone_penalty=1.5, any_cat=False),
    SplitParams(min_data_in_leaf=1, use_cegb=True, cegb_tradeoff=0.5, cegb_penalty_split=0.01,
                any_cat=False),
])
def test_the_two_bin_scan_is_the_scan_at_threshold_zero(params):
    """Features on the lanes, one split each: the gains, and with them the
    winner, of the (F, 2, 3) scan: the same splits valid, the same bits where
    the gain is the closed form, and float32 rounding apart where it goes
    through smoothed or clamped outputs (XLA fuses the two shapes' chains of
    multiplies and adds differently)."""
    rng = np.random.default_rng(1)
    f = 37
    cnt = rng.integers(0, 40, (f, 2)).astype(np.float32)
    hist = np.stack([rng.standard_normal((f, 2)).astype(np.float32) * cnt,
                     cnt * 0.25, cnt], axis=-1)
    parent = jnp.asarray(hist[0].sum(axis=0))
    hist[:, 1] = np.asarray(parent)[None, :] - hist[:, 0]
    mono = jnp.asarray(rng.integers(-1, 2, f).astype(np.int32))
    bound = jnp.asarray([-5.0, 5.0], jnp.float32)
    depth = jnp.asarray(3, jnp.int32)
    pen = jnp.asarray(rng.random(f).astype(np.float32) * 0.05)
    scale = jnp.asarray(1.0 - 0.5 * rng.random(f).astype(np.float32))
    po = jnp.asarray(0.1, jnp.float32)
    fs = best_split_per_feature(
        jnp.asarray(hist), parent, jnp.full((f,), 2, jnp.int32), jnp.zeros((f,), bool),
        jnp.zeros((f,), bool), params, mono, bound, depth, pen, scale, po)
    gain = best_split_two_bin(jnp.asarray(np.moveaxis(hist[:, 0], 0, 1)), parent, params,
                              mono, bound, depth, pen, scale, po)
    gain, want = np.asarray(gain), np.asarray(fs.gain)
    assert np.array_equal(gain > -1e29, want > -1e29)
    if params.path_smooth > 0 or params.use_monotone:
        np.testing.assert_allclose(gain, want, rtol=1e-5)
    else:
        assert np.array_equal(gain, want)
    assert (gain > -1e29).any() and (gain < -1e29).any()
    assert not np.asarray(fs.threshold_bin).any() and not np.asarray(fs.default_left).any()


def test_bundled_slots_route_as_the_decoded_column_does():
    """The fused kernel with a bundled slot's left set of bundle codes against
    the XLA form's rule (decode the bundle column to the feature's bins, then
    threshold, NaN bin and default direction): default bins, both NaN
    directions, a singleton slot, an idle slot, and a conflict row, whose
    bundle code is another member's and reads as this feature's default."""
    from lightgbm_tpu.ops.histogram_pallas import wave_row_update_pallas
    _, info = _layout()
    arrays = _arrays(info)
    decode = efb.make_bundle_decode(arrays)
    n, G = 4096, info.n_bundles
    rng = np.random.default_rng(2)
    X = np.zeros((G, n), np.uint8)
    X[0] = rng.integers(0, info.bundle_bins, n)      # any member's code, or none's
    X[1] = rng.integers(0, 7, n)
    X[2] = rng.integers(0, 7, n)
    X[3] = rng.integers(0, 12, n)
    rl = rng.integers(0, 6, n).astype(np.int32)
    #        feature thr nan dleft leaf
    slots = [(3, 1, 3, 1, 0),      # four-bin member, default 2, NaN bin 3 goes left
             (3, 2, 3, 0, 1),      # the same, NaN goes right
             (4, 0, -1, 0, 2),     # two-bin member whose default is bin 1
             (8, 2, -1, 0, 3),     # five-bin member, default 4
             (0, 3, 6, 1, 4),      # a singleton with a NaN bin
             (1, 0, -1, 0, 5)]     # idle: its slot is not active
    w = len(slots)
    feat = jnp.asarray([s[0] for s in slots], jnp.int32)
    thr = jnp.asarray([s[1] for s in slots], jnp.int32)
    nanb = jnp.asarray([s[2] for s in slots], jnp.int32)
    dleft = jnp.asarray([bool(s[3]) for s in slots])
    leaves = jnp.asarray([s[4] for s in slots], jnp.int32)
    active = jnp.asarray([1, 1, 1, 1, 1, 0], jnp.int32)
    small = jnp.asarray([1, 0, 1, 0, 1, 1], jnp.int32)
    new_ids = jnp.arange(10, 10 + w, dtype=jnp.int32)
    tab = jnp.stack([thr, nanb, dleft.astype(jnp.int32), small, leaves, new_ids, active,
                     jnp.zeros_like(thr)])
    bundled, go = efb.bundle_left_sets(arrays, feat, thr, nanb, dleft)
    assert list(np.asarray(bundled)) == [True, True, True, True, False, True]
    rl_new, ch = wave_row_update_pallas(
        jnp.asarray(X), jnp.asarray(rl), tab, feats=arrays[1][feat], cat=(bundled, go),
        bundled=True, interpret=True, pipeline="dma")
    want_rl, want_ch = rl.copy(), np.full(n, -1, np.int8)
    for j, (f, t, nb, dl, leaf) in enumerate(slots):
        if not int(active[j]):
            continue
        col = np.asarray(decode(jnp.asarray(X[info.f_bundle[f]].astype(np.int32)),
                                jnp.asarray(f)))
        left = np.where(col == nb, bool(dl), col <= t)
        here = rl == leaf
        want_ch[here & (left == bool(small[j]))] = j
        want_rl[here & ~left] = 10 + j
    assert np.array_equal(np.asarray(rl_new), want_rl)
    assert np.array_equal(np.asarray(ch), want_ch)
    # rows of every kind were there: both sides of every active slot
    for j in range(5):
        assert (want_ch == j).any() and (want_rl == 10 + j).any()
    from lightgbm_tpu.ops.histogram_pallas import traced_kernels
    assert any("wave_row_update_dma_efb_" in k for k in traced_kernels())


def _one_hot_rows(n=3000, seed=3, groups=(6, 40, 300, 3)):
    rng = np.random.default_rng(seed)
    X = np.zeros((n, 2 + sum(groups)), np.float32)
    X[:, :2] = rng.standard_normal((n, 2))
    X[rng.random(n) < 0.7, 1] = 0
    off, lv = 2, []
    for k in groups:
        p = 1.0 / np.arange(1, k + 1) ** 1.1
        c = rng.choice(k, n, p=p / p.sum())
        X[np.arange(n), off + rng.permutation(k)[c]] = 1
        lv.append(c)
        off += k
    y = (X[:, 0] + 0.9 * (lv[0] == 1) - 0.8 * (lv[1] < 3) + 0.6 * (lv[2] % 5 == 0) +
         0.5 * rng.standard_normal(n) > 0.3).astype(np.float32)
    return sp.csr_matrix(X), y


def _structure(text):
    return [line for line in text.splitlines()
            if line.startswith(("split_feature", "threshold", "decision_type", "left_child",
                                "right_child", "leaf_count"))]


def _leaf_values(text):
    return np.concatenate([np.array(line.split("=")[1].split(), np.float64)
                           for line in text.splitlines() if line.startswith("leaf_value")])


@pytest.mark.parametrize("quantized", [False, True])
def test_bundled_and_unbundled_training_grow_the_same_trees(quantized):
    """The same CSR matrix with ``enable_bundle`` on and off (the same
    mappers, the codes densified unbundled): the same splits, thresholds and
    leaf counts, and leaf values within float32 rounding (exact histograms)
    or the int8 levels' (the q8 pair).  The unbundled grower's exact endgame
    is switched off for the comparison: bundles keep the taper."""
    x, y = _one_hot_rows()
    base = dict(objective="binary", num_leaves=15, min_data_in_leaf=0,
                min_sum_hessian_in_leaf=5.0, min_data_in_bin=1, verbosity=-1,
                tree_grow_mode="wave", tpu_histogram_impl="pallas", tpu_exact_endgame=False)
    if quantized:
        base.update(use_quantized_grad=True, num_grad_quant_bins=254,
                    quant_train_renew_leaf=True)
    texts = {}
    for on in (True, False):
        p = dict(base, enable_bundle=on)
        booster = lgb.Booster(params=p, train_set=lgb.Dataset(x, y, params=p))
        for _ in range(3):
            booster.update()
        snap = booster.train_record.snapshot()
        assert snap["grower"]["efb"] is on and snap["grower"]["row_update"] == "kernel"
        if on:
            assert snap["efb"]["features"] > 10 * snap["efb"]["bundles"]
            assert snap["efb"]["bundled_features"] > 300 and snap["efb"]["conflict_rows"] == 0
            assert snap["setup_seconds"]["find_bundles"] > 0
            assert snap["setup_seconds"]["bundle_matrix"] > 0
            assert snap["setup_seconds"]["bin_matrix"] >= snap["setup_seconds"]["bundle_matrix"]
        else:
            assert snap["efb"] == {}
        texts[on] = booster.model_to_string()
        if on:
            sparse_pred = booster.predict(x)
    assert _structure(texts[True]) == _structure(texts[False])
    np.testing.assert_allclose(_leaf_values(texts[True]), _leaf_values(texts[False]),
                               rtol=2e-3 if quantized else 2e-5, atol=1e-7)
    # prediction from raw (unbundled) values, sparse rows taken in slices
    np.testing.assert_allclose(sparse_pred, lgb.Booster(model_str=texts[True]).predict(
        x.toarray()), rtol=1e-6)


def test_the_kernel_route_grows_the_xla_routes_trees():
    """q8 sums are integers whatever builds them: the Pallas kernels with the
    fused row update (bundled slots) against the XLA histograms with the XLA
    (W, N) row update, tree for tree."""
    x, y = _one_hot_rows(seed=4)
    texts = []
    for impl in ("pallas", "onehot"):
        p = dict(objective="binary", num_leaves=15, min_data_in_leaf=0,
                 min_sum_hessian_in_leaf=5.0, min_data_in_bin=1, verbosity=-1,
                 tree_grow_mode="wave", tpu_histogram_impl=impl, enable_bundle=True,
                 use_quantized_grad=True, num_grad_quant_bins=254,
                 quant_train_renew_leaf=True)
        booster = lgb.Booster(params=p, train_set=lgb.Dataset(x, y, params=p))
        for _ in range(3):
            booster.update()
        paths = booster.train_record.snapshot()["grower"]
        assert paths["row_update"] == ("kernel" if impl == "pallas" else "xla") and paths["efb"]
        texts.append(booster.model_to_string())
    assert _structure(texts[0]) == _structure(texts[1])
    # renewed from unquantised gradients: float32 sums in another order
    np.testing.assert_allclose(_leaf_values(texts[0]), _leaf_values(texts[1]), rtol=2e-4)


def test_conflicts_are_counted_and_listed_exactly(monkeypatch):
    """Two columns of one bundle set in one row: the column with the larger
    index keeps the row, the other is listed and trained on as zero."""
    import functools
    monkeypatch.setattr(efb, "find_bundles",
                        functools.partial(efb.find_bundles, conflict_rate=0.02))
    rng = np.random.default_rng(5)
    n = 2000
    X = np.zeros((n, 12), np.float32)
    X[:, 0] = rng.standard_normal(n)
    a = rng.integers(0, 5, n)
    X[np.arange(n), 1 + a] = 1                       # exactly exclusive
    for j in range(6, 12):                           # rare columns that overlap them
        X[rng.choice(n, 12, replace=False), j] = 1
    y = (X[:, 0] + X[:, 2] > 0.5).astype(np.float32)
    p = dict(objective="binary", num_leaves=7, min_data_in_leaf=1, min_data_in_bin=1,
             verbosity=-1, enable_bundle=True)
    ds = lgb.Dataset(sp.csr_matrix(X), y, params=p).construct()
    rows, cols = ds.efb_conflicts()
    assert len(rows) > 0 and ds.efb.record()["conflict_rows"] == len(np.unique(rows))
    assert (X[rows, cols] != 0).all()
    # what the bundled matrix holds is the raw matrix with the listed entries zeroed
    seen = X.copy()
    seen[rows, cols] = 0
    info = ds.efb
    for f in range(12):
        g = info.f_bundle[f]
        col = ds.X_binned[:, g].astype(np.int32)
        if info.f_single[f]:
            continue
        mine = col == info.f_offset[f]               # two-bin members: one code each
        assert np.array_equal(mine, seen[:, f] != 0), f
    dense = lgb.Dataset(X.astype(np.float64), y, params=p).construct()
    assert dense.efb.record()["conflict_rows"] == ds.efb.record()["conflict_rows"]
    assert np.array_equal(dense.X_binned, ds.X_binned)
    none = lgb.Dataset(X, y, params=dict(p, enable_bundle=False)).construct()
    assert len(none.efb_conflicts()[0]) == 0


def test_sparse_rows_are_predicted_in_slices(monkeypatch):
    from lightgbm_tpu import basic
    x, y = _one_hot_rows(n=1500)
    p = dict(objective="binary", num_leaves=7, min_data_in_bin=1, verbosity=-1)
    booster = lgb.train(p, lgb.Dataset(x, y, params=p), 3)
    whole = booster.predict(x.toarray())
    shapes = []
    real = booster._gbdt.predict
    monkeypatch.setattr(booster._gbdt, "predict",
                        lambda d, **k: (shapes.append(d.shape), real(d, **k))[1])
    monkeypatch.setattr(basic, "_SPARSE_PREDICT_CELLS", 400 * x.shape[1])
    np.testing.assert_array_equal(booster.predict(x), whole)
    assert shapes == [(400, x.shape[1])] * 3 + [(300, x.shape[1])]
    np.testing.assert_array_equal(booster.predict(x[:50].tocsc()), whole[:50])
    leaves = booster.predict(x, pred_leaf=True)
    assert leaves.shape == (1500, 3)
