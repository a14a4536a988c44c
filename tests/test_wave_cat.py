"""Integer + categorical + missing data through the wave grower (PR 34):
the fused row-update kernel routes categorical slots by their bit sets (the
Pallas route interpreted, on the CPU), held to the sequential partitioned
grower and to the XLA form of the row update; a row reaches at prediction the
leaf training gave it, whatever its categorical value; ``TrainRecord`` says
how many splits are categorical and which static paths the grower took."""

import numpy as np
import pytest

import lightgbm_tpu as lgb

N = 6000
CATS = [3, 4, 5]
BASE = {"objective": "binary", "num_leaves": 15, "max_bin": 31,
        "min_data_in_leaf": 20, "min_data_per_group": 20, "cat_smooth": 10.0,
        "learning_rate": 0.2, "verbosity": -1, "categorical_feature": CATS}
KERNEL = {"tree_grow_mode": "wave", "tpu_histogram_impl": "pallas",
          "tpu_pallas_pipeline": "dma"}
XLA = {"tree_grow_mode": "wave", "tpu_histogram_impl": "onehot"}
Q8 = {"use_quantized_grad": True, "num_grad_quant_bins": 254,
      "quant_train_renew_leaf": True}


def _data(n=N, seed=3):
    """3 count columns with NaN, 3 categorical ones (3 / 40 / 600 ids, Zipf,
    permuted ids, all with NaN: a column without would make one-vs-rest
    splits of its last two categories exact mirror ties; the third folds
    most of its ids away at 31 bins)."""
    rng = np.random.RandomState(seed)
    xi = np.floor(np.exp(rng.randn(n, 3)))
    logit = 0.6 * np.log1p(xi[:, 0]) - 0.4 * np.log1p(xi[:, 2])
    xi[rng.rand(n, 3) < [0.3, 0.0, 0.6]] = np.nan
    cols = []
    for card in (3, 40, 600):
        rank = (rng.zipf(1.5, n) - 1) % card
        logit = logit + 0.8 * rng.randn(card)[rank]
        cols.append(rng.permutation(card)[rank].astype(np.float64))
    xc = np.stack(cols, axis=1)
    xc[rng.rand(n, 3) < [0.2, 0.1, 0.3]] = np.nan
    y = (logit + rng.randn(n) > 0.4).astype(np.float64)
    return np.concatenate([xi, xc], axis=1), y


def _train(params, rounds=3):
    X, y = _data()
    return lgb.train(dict(BASE, **params),
                     lgb.Dataset(X, y, categorical_feature=CATS), rounds), X


def _shape(bst):
    """Per tree: (split feature, threshold or sorted left set) of every node."""
    out = []
    for t in bst._gbdt.models:
        k = t.num_leaves - 1
        out.append([(int(t.split_feature[i]),
                     tuple(t.cat_values(i)) if t.decision_type[i] & 1
                     else round(float(t.threshold[i]), 6)) for i in range(k)])
    return out


def test_wave1_kernel_route_grows_the_sequential_growers_trees():
    """Exact arithmetic, one split a wave: the kernel-routed wave grower and
    the partitioned grower choose the same splits, left sets included."""
    seq, X = _train({"tree_grow_mode": "partition"})
    wav, _ = _train(dict(KERNEL, tpu_wave_size=1))
    assert _shape(wav) == _shape(seq)
    assert any(isinstance(v, tuple) for _, v in _shape(wav)[0])
    np.testing.assert_allclose(wav.predict(X), seq.predict(X), atol=2e-4)


@pytest.mark.parametrize("arith, atol", [("exact", 2e-4), ("q8", 2e-2)])
def test_kernel_route_against_the_xla_row_update(arith, atol):
    """Whole waves: the same grower with its rows routed by the kernel and by
    the XLA form.  Exact arithmetic: the same splits (the two histogram
    implementations round differently: predictions to 2e-4).  q8: the
    emulated int sums of the XLA route draw other rounding noise than the
    kernels' stream, so the trees may part at a near-tie: predictions within
    2e-2 of each other, stated."""
    extra = Q8 if arith == "q8" else {}
    ker, X = _train(dict(KERNEL, **extra))
    xla, _ = _train(dict(XLA, **extra))
    if arith == "exact":
        assert _shape(ker) == _shape(xla)
    assert ker._gbdt.learner.grower_paths["row_update"] == "kernel"
    assert xla._gbdt.learner.grower_paths["row_update"] == "xla"
    assert np.mean(np.abs(ker.predict(X) - xla.predict(X))) < atol


@pytest.fixture(scope="module")
def kernel_q8():
    return _train(dict(KERNEL, **Q8), rounds=4)


def test_training_leaf_is_prediction_leaf_on_the_training_rows(kernel_q8):
    """Every training row, walked on its RAW values, lands in the leaf whose
    count training stated: missing integers, missing categories and the
    categories the binning folded away included."""
    bst, X = kernel_q8
    leaves = bst.predict(X, pred_leaf=True)
    for t, tree in enumerate(bst._gbdt.models):
        got = np.bincount(leaves[:, t].astype(np.int64), minlength=tree.num_leaves)
        np.testing.assert_array_equal(got, tree.leaf_count[:tree.num_leaves])
    mapper = bst._gbdt.train_set.bin_mappers[5]
    assert mapper.num_bin == 31 and len(mapper.cat_to_bin) == 30     # 570 ids folded


@pytest.mark.parametrize("value", ["folded", "unseen", "beyond", "negative",
                                   "fraction", "nan"])
def test_every_unbinned_category_takes_the_missing_ones_path(kernel_q8, value):
    """A folded, never seen, negative or non-integer category reaches the leaf
    a missing one reaches (training gave all of them bin 0, which no split
    sends left), through the tree walk, the dense predictor and the
    booster's own ``predict``; a categorical node never sets default_left."""
    bst, X = kernel_q8
    mapper = bst._gbdt.train_set.bin_mappers[5]
    folded = next(c for c in range(600) if c not in mapper.cat_to_bin)
    v = {"folded": folded, "unseen": 601.0, "beyond": 1e9, "negative": -3.0,
         "fraction": float(next(iter(mapper.cat_to_bin))) + 0.5, "nan": np.nan}[value]
    rows = X[:512].copy()
    as_nan = rows.copy()
    rows[:, 3:] = v
    as_nan[:, 3:] = np.nan
    if value == "folded":
        rows[:, 3:5] = np.nan           # the id is folded in column 5 alone
    want = bst.predict(as_nan, pred_leaf=True)
    np.testing.assert_array_equal(bst.predict(rows, pred_leaf=True), want)
    for compiler in ("walk", "dense"):
        pred = bst.to_predictor(compiler=compiler)
        np.testing.assert_allclose(pred.predict(rows), pred.predict(as_nan), atol=1e-6)
    for tree in bst._gbdt.models:
        cat = (tree.decision_type[:tree.num_leaves - 1] & 1) != 0
        assert cat.any() and not (tree.decision_type[:tree.num_leaves - 1][cat] & 2).any()
        for i in np.flatnonzero(cat):
            assert min(tree.cat_values(i)) >= 0


def test_the_record_counts_categorical_splits_and_states_the_growers_paths(kernel_q8):
    bst, _ = kernel_q8
    snap = bst._gbdt.train_record.snapshot()
    assert snap["grower"] == {
        "ramp": False, "endgame": False, "scatter": False, "voting": False,
        "efb": False, "any_cat": True, "sampled": False, "row_update": "kernel",
        "hist_acc_rows": 0}
    for row, tree in zip(snap["trees"], bst._gbdt.models):
        k = tree.num_leaves - 1
        assert row["cat_splits"] == int(np.sum(tree.decision_type[:k] & 1)) > 0
    numeric = lgb.train(
        dict(BASE, **KERNEL, categorical_feature=[]),
        lgb.Dataset(*_data()), 1)
    snap = numeric._gbdt.train_record.snapshot()
    assert snap["grower"]["any_cat"] is False and snap["grower"]["endgame"] is True
    assert snap["grower"]["row_update"] == "kernel"
    assert [t["cat_splits"] for t in snap["trees"]] == [0]
    part = lgb.train(dict(BASE, tree_grow_mode="partition"),
                     lgb.Dataset(*_data(), categorical_feature=CATS), 1)
    snap = part._gbdt.train_record.snapshot()
    assert snap["grower"] == {} and snap["trees"][0]["cat_splits"] > 0


def test_a_numeric_data_set_traces_no_categorical_scan_and_the_numeric_kernel():
    """``any_cat`` is static: a data set without categorical columns traces
    neither the search's scope nor the categorical entry of the row update."""
    from lightgbm_tpu.ops import histogram_pallas as hp
    X, y = _data()

    def grower_text(cats):
        p = dict(BASE, **KERNEL, categorical_feature=cats)
        bst = lgb.Booster(params=p, train_set=lgb.Dataset(
            X, y, params=p, categorical_feature=cats))
        g, seen = bst._gbdt.learner, {}
        real = g._grow

        def spy(*a, **k):
            seen["text"] = real.lower(*a, **k).as_text(debug_info=True)
            return real(*a, **k)

        g._grow = spy
        bst.update()
        return seen["text"]

    numeric, mixed = grower_text([]), grower_text(CATS)
    assert "lgbm.wave.cat_scan" not in numeric and "row_update_dma_cat" not in numeric
    assert "lgbm.wave.cat_scan" in mixed and "lgbm_wave_row_update_dma_cat_w" in mixed
    assert "lgbm_wave_row_update_dma_w" in numeric
    assert any("row_update_dma_cat" in k for k in hp.traced_kernels())
