"""Distributed learner tests on the 8-device CPU mesh (SURVEY.md §4: the
reference's test_dask.py pattern — N workers on localhost, compare to
serial — becomes mesh-sharded training compared to the serial learner)."""

import jax
import numpy as np
import pytest


import lightgbm_tpu as lgb

SMALL = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1}


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(11)
    n = 700  # deliberately not divisible by 8 to exercise padding
    X = rng.randn(n, 6)
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 2] ** 2 * 0.2) > 0).astype(np.float64)
    return X, y


def test_mesh_available():
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("tree_learner", [
    "data", "feature", "voting"])
def test_parallel_matches_serial(tree_learner, data):
    X, y = data
    p = {}
    for tl in ("serial", tree_learner):
        bst = lgb.train({**SMALL, "objective": "binary", "tree_learner": tl},
                        lgb.Dataset(X, y), 5)
        p[tl] = bst.predict(X)
    np.testing.assert_allclose(p[tree_learner], p["serial"], atol=2e-5)


def test_data_parallel_regression(data):
    X, y = data
    yr = X[:, 0] * 2 + np.sin(X[:, 1])
    serial = lgb.train({**SMALL, "objective": "regression"},
                       lgb.Dataset(X, yr), 5).predict(X)
    dp = lgb.train({**SMALL, "objective": "regression",
                    "tree_learner": "data"}, lgb.Dataset(X, yr), 5).predict(X)
    np.testing.assert_allclose(dp, serial, atol=1e-4)


@pytest.mark.parametrize("tree_learner", [
    "data", "feature"])
def test_parallel_bagging_goss_matches_serial(tree_learner, data):
    """Sampling paths under shard_map: bagging masks and GOSS gradient
    amplification must reproduce the serial learner exactly (the mask is
    computed host-side and sharded with the rows)."""
    X, y = data
    for extra in ({"bagging_fraction": 0.6, "bagging_freq": 1},
                  {"boosting": "goss", "top_rate": 0.3, "other_rate": 0.2,
                   "learning_rate": 0.5}):
        p = {}
        for tl in ("serial", tree_learner):
            bst = lgb.train({**SMALL, "objective": "binary",
                             "tree_learner": tl, **extra},
                            lgb.Dataset(X, y), 4)
            p[tl] = bst.predict(X)
        np.testing.assert_allclose(p[tree_learner], p["serial"], atol=2e-5)


def test_parallel_multiclass_matches_serial(data):
    X, _ = data
    rng = np.random.RandomState(5)
    y = rng.randint(0, 3, len(X)).astype(np.float64)
    p = {}
    for tl in ("serial", "data"):
        bst = lgb.train({**SMALL, "objective": "multiclass", "num_class": 3,
                         "tree_learner": tl}, lgb.Dataset(X, y), 3)
        p[tl] = bst.predict(X)
    np.testing.assert_allclose(p["data"], p["serial"], atol=2e-5)


def test_parallel_categorical_nan_matches_serial():
    rng = np.random.RandomState(9)
    n = 640
    c = rng.randint(0, 8, n).astype(float)
    x1 = rng.randn(n)
    x1[rng.rand(n) < 0.15] = np.nan  # NaN bin routing under shard_map
    y = np.where(c % 2 == 0, 1.5, -1.5) + np.nan_to_num(x1) * 0.3
    X = np.stack([c, x1], 1)
    p = {}
    for tl in ("serial", "data"):
        bst = lgb.train({**SMALL, "objective": "regression",
                         "tree_learner": tl, "cat_smooth": 1.0,
                         "min_data_per_group": 1},
                        lgb.Dataset(X, y, categorical_feature=[0]), 4)
        p[tl] = bst.predict(X)
    np.testing.assert_allclose(p["data"], p["serial"], atol=2e-5)


def test_voting_with_many_features():
    rng = np.random.RandomState(1)
    n, f = 640, 24
    X = rng.randn(n, f)
    y = (X[:, :4].sum(axis=1) > 0).astype(np.float64)
    bst = lgb.train({**SMALL, "objective": "binary", "tree_learner": "voting",
                     "top_k": 5}, lgb.Dataset(X, y), 5)
    p = bst.predict(X)
    # voting restricts aggregated features but must still learn the signal
    order = np.argsort(-p)
    assert y[order[: n // 4]].mean() > 0.8


@pytest.mark.parametrize("fed", ["matrix", "blocks"])
def test_data_parallel_wave_matches_serial_wave(data, fed):
    """The wave grower under shard_map (one histogram psum per wave) must
    reproduce the single-device wave grower: psum'd histograms make every
    shard's candidate scans identical.  Fed as row blocks, the share is
    still the whole: the same trees as the serial learner on the matrix."""
    X, y = data
    p = {**SMALL, "objective": "binary", "tree_grow_mode": "wave"}
    serial = lgb.train(p, lgb.Dataset(X, y), 5).predict(X)
    rows = X if fed == "matrix" else [X[:300], X[300:301], X[301:]]
    dp = lgb.train({**p, "tree_learner": "data"},
                   lgb.Dataset(rows, y), 5).predict(X)
    np.testing.assert_allclose(dp, serial, atol=2e-5)


def test_four_device_trees_equal_serial_trees_with_the_ramp_off():
    """The tie between the share and the whole on the cell's path: q8
    through the Pallas kernels, four devices, rows in blocks, the ramp off
    and rounding to nearest (with the ramp on each shard strides its own
    rows for the provisional subsample, and the trees differ by design):
    the integer histograms sum exactly, so the trees are the serial ones."""
    rng = np.random.RandomState(5)
    X = rng.randn(4000, 6).astype(np.float32)
    y = ((X[:, 0] + 0.5 * X[:, 1] - 0.2 * X[:, 2] ** 2) > 0).astype(np.float32)
    p = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
         "min_data_in_leaf": 5, "verbosity": -1, "tree_grow_mode": "wave",
         "tpu_histogram_impl": "pallas", "use_quantized_grad": True,
         "quant_train_renew_leaf": True, "stochastic_rounding": False,
         "tpu_speculative_ramp": False}
    serial = lgb.train(p, lgb.Dataset(X, y), 3)
    dp = lgb.train({**p, "tree_learner": "data", "num_devices": 4},
                   lgb.Dataset([X[:1024], X[1024:3000], X[3000:]], y), 3)

    def structure(bst):
        return [ln for ln in bst.model_to_string().splitlines()
                if ln.startswith(("split_feature=", "threshold=",
                                  "left_child=", "right_child=",
                                  "leaf_count="))]
    assert structure(dp) == structure(serial)
    np.testing.assert_allclose(dp.predict(X), serial.predict(X), atol=2e-6)


def test_data_parallel_wave_bagging_multiclass(data):
    X, y = data
    rng = np.random.RandomState(3)
    ym = (rng.rand(len(y)) < 0.3).astype(int) + y.astype(int)
    p = {**SMALL, "objective": "multiclass", "num_class": 3,
         "tree_grow_mode": "wave", "bagging_fraction": 0.7,
         "bagging_freq": 1}
    serial = lgb.train(p, lgb.Dataset(X, ym.astype(float)), 4).predict(X)
    dp = lgb.train({**p, "tree_learner": "data"},
                   lgb.Dataset(X, ym.astype(float)), 4).predict(X)
    np.testing.assert_allclose(dp, serial, atol=5e-5)


@pytest.mark.parametrize("extra", [
    {"extra_trees": True},
    {"feature_fraction_bynode": 0.5},
    {"cegb_tradeoff": 0.5, "cegb_penalty_split": 0.05},
    {"interaction_constraints": "[0,3],[1,2]"},
])
def test_dp_wave_extras_match_serial_wave(extra, data):
    """The round-4 DP-wave feature completion: extra_trees / bynode
    sampling / CEGB / interaction constraints under tree_learner=data
    reproduce the serial wave grower exactly (replicated node-key
    streams, identical node ids; parallel_tree_learner.h:54's 'DP wraps
    the serial learner' contract)."""
    X, y = data
    preds = {}
    for tl in ("serial", "data"):
        bst = lgb.train({**SMALL, "objective": "binary",
                         "tree_learner": tl, "tree_grow_mode": "wave",
                         **extra}, lgb.Dataset(X, y), 5)
        preds[tl] = bst.predict(X)
    np.testing.assert_allclose(preds["data"], preds["serial"], atol=2e-5)


def test_wave_extras_quality_vs_partitioned(data):
    """Serial wave with per-node sampling stays quality-par with the
    partitioned grower's implementation of the same features."""
    X, y = data
    ll = {}
    for mode in ("wave", "partition"):
        bst = lgb.train({**SMALL, "objective": "binary",
                         "tree_grow_mode": mode, "extra_trees": True,
                         "feature_fraction_bynode": 0.7,
                         "interaction_constraints": "[0,1,3],[2,4,5]"},
                        lgb.Dataset(X, y), 8)
        pred = np.clip(bst.predict(X), 1e-9, 1 - 1e-9)
        ll[mode] = -np.mean(y * np.log(pred) + (1 - y) * np.log(1 - pred))
    assert ll["wave"] < ll["partition"] * 1.15 + 5e-3


def test_wave_interaction_constraints_respected(data):
    """Trees grown by the wave grower never mix features across
    constraint groups on one branch."""
    X, y = data
    bst = lgb.train({**SMALL, "objective": "binary",
                     "tree_grow_mode": "wave",
                     "interaction_constraints": "[0,3],[1,2],[4,5]"},
                    lgb.Dataset(X, y), 6)
    groups = [{0, 3}, {1, 2}, {4, 5}]
    for tree in bst._gbdt.models:
        nl = int(tree.num_leaves)
        if nl <= 1:
            continue
        # walk root->leaf paths collecting used features
        def walk(node, used):
            f = int(tree.split_feature[node])
            used = used | {f}
            assert any(used <= g for g in groups), used
            for child in (int(tree.left_child[node]),
                          int(tree.right_child[node])):
                if child >= 0:
                    walk(child, used)
        walk(0, set())
