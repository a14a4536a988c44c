"""Monotone constraint tests (reference pattern:
tests/python_package_test/test_engine.py:1214-1327 — train with ±1
constraints and assert predictions are monotone in the constrained feature
while other features vary)."""

import numpy as np
import pytest


import lightgbm_tpu as lgb


def _gen(n=1200, seed=0):
    rng = np.random.RandomState(seed)
    x0 = rng.rand(n)          # constrained +1
    x1 = rng.rand(n)          # constrained -1
    x2 = rng.rand(n)          # free
    # true relationship is NOT monotone in x0/x1 so the constraint binds
    y = (5 * x0 + np.sin(10 * np.pi * x0)
         - 5 * x1 - np.cos(10 * np.pi * x1)
         + 10 * x2 + rng.randn(n) * 0.1)
    return np.stack([x0, x1, x2], 1), y


def _is_monotone(bst, feature, sign, n_checks=20):
    rng = np.random.RandomState(99)
    grid = np.linspace(0.0, 1.0, 101)
    for _ in range(n_checks):
        row = rng.rand(3)
        batch = np.tile(row, (101, 1))
        batch[:, feature] = grid
        pred = bst.predict(batch)
        diffs = np.diff(pred)
        if sign > 0 and (diffs < -1e-9).any():
            return False
        if sign < 0 and (diffs > 1e-9).any():
            return False
    return True


PARAMS = {"objective": "regression", "num_leaves": 31, "verbosity": -1,
          "metric": "l2", "monotone_constraints": [1, -1, 0]}


def test_unconstrained_is_not_monotone():
    X, y = _gen()
    bst = lgb.train({k: v for k, v in PARAMS.items()
                     if k != "monotone_constraints"}, lgb.Dataset(X, y), 60)
    assert not _is_monotone(bst, 0, +1)


@pytest.mark.parametrize("extra", [{}, {"monotone_penalty": 2.0}])
def test_monotone_serial(extra):
    X, y = _gen()
    bst = lgb.train({**PARAMS, **extra}, lgb.Dataset(X, y), 60)
    assert _is_monotone(bst, 0, +1)
    assert _is_monotone(bst, 1, -1)
    # the model still learns something useful
    resid = np.mean((bst.predict(X) - y) ** 2)
    assert resid < np.var(y) * 0.5


def test_monotone_config_string_alias():
    X, y = _gen()
    bst = lgb.train({**PARAMS, "monotone_constraints": "1,-1,0"},
                    lgb.Dataset(X, y), 40)
    assert _is_monotone(bst, 0, +1)


def test_monotone_data_parallel():
    X, y = _gen()
    bst = lgb.train({**PARAMS, "tree_learner": "data", "num_devices": 4},
                    lgb.Dataset(X, y), 40)
    assert _is_monotone(bst, 0, +1)
    assert _is_monotone(bst, 1, -1)


def test_monotone_feature_parallel():
    X, y = _gen()
    bst = lgb.train({**PARAMS, "tree_learner": "feature", "num_devices": 4},
                    lgb.Dataset(X, y), 30)
    assert _is_monotone(bst, 0, +1)


def test_monotone_penalty_reduces_monotone_splits():
    X, y = _gen()
    b0 = lgb.train(PARAMS, lgb.Dataset(X, y), 40)
    # small penalties only push monotone splits deeper; a penalty larger
    # than the max depth suppresses them outright (factor ~eps at d < p-1)
    b9 = lgb.train({**PARAMS, "monotone_penalty": 10.0}, lgb.Dataset(X, y), 40)

    def mono_split_count(bst):
        total = 0
        for tree in bst._gbdt.models:
            sf = tree.split_feature[:tree.num_leaves - 1]
            total += int(np.sum((sf == 0) | (sf == 1)))
        return total
    # high penalty discourages splits on the constrained features
    assert mono_split_count(b9) < mono_split_count(b0)


def test_monotone_intermediate_wave():
    """monotone_constraints_method=intermediate on the wave grower:
    constraints hold under the region-box propagation, and the looser
    sibling-output bounds fit at least as well as basic."""
    X, y = _gen()
    base = {**PARAMS, "tree_grow_mode": "wave"}
    bst_b = lgb.train({**base, "monotone_constraints_method": "basic"},
                      lgb.Dataset(X, y), 60)
    bst_i = lgb.train({**base, "monotone_constraints_method": "intermediate"},
                      lgb.Dataset(X, y), 60)
    assert _is_monotone(bst_i, 0, +1)
    assert _is_monotone(bst_i, 1, -1)
    mse_b = np.mean((bst_b.predict(X) - y) ** 2)
    mse_i = np.mean((bst_i.predict(X) - y) ** 2)
    # intermediate is less constraining: fit must not be (meaningfully)
    # worse than basic
    assert mse_i <= mse_b * 1.02 + 1e-6
    # 'advanced' downgrades to intermediate with a warning, still monotone
    bst_a = lgb.train({**base, "monotone_constraints_method": "advanced"},
                      lgb.Dataset(X, y), 30)
    assert _is_monotone(bst_a, 0, +1)
