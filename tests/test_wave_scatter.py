"""Feature-sliced reduce-scatter histogram merging on the DP wave path
(ISSUE 5 tentpole; learner/wave.py use_scatter + WaveDPStrategy.
reduce_hist_scatter — the reference DP learner's ReduceScatter
refinement, data_parallel_tree_learner.cpp:155-173, amortized over the
wave's channels).

Contract under test:
  * bit-identity — with ``tpu_dp_hist_scatter=True`` the trained tree is
    IDENTICAL to the full-batch-psum DP path and to the serial grower
    (quantized path: bit-for-bit, integer channel sums reduce exactly;
    f32: prediction-tolerance, like the existing DP parity tests);
  * collective shape — the traced program contains exactly one
    ``reduce_scatter`` per histogram-merge site and ZERO full-histogram
    ``psum``s: every remaining psum operand is O(W*k) winner-exchange /
    leaf-totals sized;
  * fallback — categorical / forced-split configs with the flag ON fall
    back to the psum merge and still reproduce serial training;
  * telemetry — collectives_snapshot() shows the per-pass histogram
    bytes dropping by >= 4x at k=8.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner.wave import make_wave_grow_fn
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy
from lightgbm_tpu.parallel.mesh import get_mesh, shard_wave_grower

F, B, LEAVES, WAVE = 6, 64, 13, 4


def _mk_data(seed=0):
    rng = np.random.RandomState(seed)
    n = 8 * 4096
    bins = rng.randint(0, B - 1, (F, n)).astype(np.uint8)
    logit = (bins[0].astype(np.float32) / B - 0.5) * 3 + \
        ((bins[1] > 40).astype(np.float32) - 0.5) * 2
    y = (logit + rng.randn(n) * 0.7 > 0).astype(np.float32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    mask = np.ones(n, np.float32)
    return (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask))


def _mk_grow(strategy, quantized=True, spec=False):
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    return make_wave_grow_fn(
        num_leaves=LEAVES, num_features=F, max_bins=B, max_depth=0,
        split_params=sp, hist_impl="pallas", any_cat=False, interpret=True,
        jit=False, wave_size=WAVE, quantized=quantized, stochastic=False,
        spec_ramp=spec, spec_tol=0.02, strategy=strategy)


def _wrap_dp(grow, mesh, ax):
    return shard_wave_grower(
        lambda X_T, g, h, m, nb, ic, hn, mono, cp, fm: grow(
            X_T, g, h, m, nb, ic, hn, mono, cp, (), fm), mesh, ax)


def _meta_args():
    return (jnp.full((F,), B, jnp.int32), jnp.zeros((F,), bool),
            jnp.zeros((F,), bool), jnp.zeros((F,), jnp.int32),
            jnp.zeros((F,), jnp.float32), jnp.ones((F,), bool))


def _serial_call(grow, data):
    bins, grad, hess, mask = data
    nb, ic, hn, mono, cp, fm = _meta_args()
    return grow(bins, grad, hess, mask, nb, ic, hn, mono, cp, (), fm)


BITWISE = ("num_leaves", "split_feature", "threshold_bin", "nan_bin",
           "decision_type", "left_child", "right_child", "row_leaf")


def test_scatter_matches_allreduce_and_serial_bitwise():
    """Quantized DP wave: scatter == psum == serial, bit-for-bit (the
    endgame engages at 13 leaves / wave 4, so the slice-local bank and
    the per-commit winner exchange are exercised too)."""
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    data = _mk_data()
    args = data + _meta_args()
    t_ser = _serial_call(_mk_grow(None), data)
    t_ar = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=8)),
                    mesh, ax)(*args)
    t_sc = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=8,
                                            hist_scatter=True)),
                    mesh, ax)(*args)
    for name in BITWISE + ("split_gain", "leaf_value", "leaf_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_sc, name)),
            np.asarray(getattr(t_ar, name)),
            err_msg=f"scatter != allreduce: {name}")
    for name in BITWISE:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_sc, name)),
            np.asarray(getattr(t_ser, name)),
            err_msg=f"scatter != serial: {name}")
    np.testing.assert_allclose(np.asarray(t_sc.leaf_value),
                               np.asarray(t_ser.leaf_value),
                               rtol=0, atol=1e-6)
    assert int(t_sc.hist_passes) == int(t_ser.hist_passes)


def test_scatter_spec_ramp_rides_the_scatter():
    """Spec ramp + scatter: the provisional passes reduce-scatter their
    subsample batches and the committed tree still equals serial spec
    growth bit-for-bit on the quantized path."""
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    data = _mk_data(seed=3)
    args = data + _meta_args()
    t_ser = _serial_call(_mk_grow(None, spec=True), data)
    t_sc = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=8,
                                            hist_scatter=True), spec=True),
                    mesh, ax)(*args)
    for name in BITWISE:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_sc, name)),
            np.asarray(getattr(t_ser, name)), err_msg=name)
    assert int(t_sc.hist_passes) == int(t_ser.hist_passes)


# ---------------------------------------------------------------------------
# Traced-program shape: one reduce_scatter per merge site, zero
# full-histogram psums, O(W*k) winner exchange.  The jaxpr traversal is
# the shared analysis.ir walker (this file's local copy moved there).
# ---------------------------------------------------------------------------

from lightgbm_tpu.analysis.ir import collect_collectives as _collectives_of


def test_scatter_traced_collectives_shape():
    """Jaxpr-level assertion (test_specramp style): the scatter program
    holds exactly one reduce_scatter per histogram-merge site (root +
    wave body + endgame body = 3 for the non-spec config), NO psum as
    large as a histogram batch, and a winner exchange per scan site."""
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    args = _mk_data() + _meta_args()
    g_sc = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=8,
                                            hist_scatter=True)), mesh, ax)
    g_ar = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=8)), mesh, ax)
    coll_sc = _collectives_of(lambda *a: g_sc(*a), *args)
    coll_ar = _collectives_of(lambda *a: g_ar(*a), *args)

    # reduce-scatter name differs across jax versions; find it
    rs_names = [k for k in coll_sc if "reduce_scatter" in k]
    assert rs_names, f"no reduce_scatter traced: {sorted(coll_sc)}"
    n_rs = sum(len(coll_sc[k]) for k in rs_names)
    # one per merge site: root pass, wave-body pass, endgame-bank pass
    assert n_rs == 3, (n_rs, coll_sc)
    assert not any("reduce_scatter" in k for k in coll_ar), coll_ar

    # the allreduce program psums full (c, F, B, 3) histogram batches;
    # the scatter program must have NO psum bigger than the O(W*k)
    # winner-exchange payload / leaf-totals vectors
    hist_batch = WAVE * F * B * 3
    big_ar = [s for s in coll_ar.get("psum", []) if s >= hist_batch]
    assert big_ar, "allreduce baseline lost its histogram psum?"
    exchange_cap = 16 * max(2 * WAVE, LEAVES)
    big_sc = [s for s in coll_sc.get("psum", []) if s > exchange_cap]
    assert not big_sc, f"full-histogram psum leaked into scatter: {big_sc}"
    # winner exchange present: one pmax+pmin pair per scan site (root,
    # wave-body children, endgame-commit children)
    assert len(coll_sc.get("pmax", [])) >= 3
    assert len(coll_sc.get("pmin", [])) >= 3
    assert all(s <= exchange_cap for s in coll_sc["pmax"])


def test_scatter_telemetry_byte_ratio():
    """collectives_snapshot(): >= 4x fewer histogram bytes per merge at
    k=8 (F=6 pads to 8 blocks of 1 -> a 6x residency drop)."""
    from lightgbm_tpu.telemetry import _config as tele_config
    from lightgbm_tpu.telemetry.train_record import (collectives_reset,
                                                     collectives_snapshot)
    if not tele_config.enabled():
        pytest.skip("telemetry disabled via LGBM_TPU_TELEMETRY=0")
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    args = _mk_data() + _meta_args()
    collectives_reset()
    g_sc = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=8,
                                            hist_scatter=True)), mesh, ax)
    jax.make_jaxpr(lambda *a: g_sc(*a))(*args)  # trace -> tally
    snap_sc = collectives_snapshot()
    collectives_reset()
    g_ar = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=8)), mesh, ax)
    jax.make_jaxpr(lambda *a: g_ar(*a))(*args)
    snap_ar = collectives_snapshot()
    collectives_reset()

    sc = snap_sc["data_parallel/wave/hist_reduce_scatter"]
    ar = snap_ar["data_parallel/wave/hist_psum"]
    assert sc["count"] == ar["count"] == 3  # root + body + endgame
    per_pass_sc = sc["bytes"] / sc["count"]
    per_pass_ar = ar["bytes"] / ar["count"]
    assert per_pass_ar >= 4 * per_pass_sc, (per_pass_ar, per_pass_sc)
    # and the winner exchange was tallied
    assert "data_parallel/wave/winner_exchange" in snap_sc


# ---------------------------------------------------------------------------
# Public-API parity: the config flag, NaN/monotone on the scatter path,
# cats + forced splits falling back to the psum merge
# ---------------------------------------------------------------------------

SMALL = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1,
         "tree_grow_mode": "wave"}


@pytest.mark.parametrize("extra", [
    {},
    {"monotone_constraints": [1, 0, 0, 0, 0, 0]},
])
def test_dp_scatter_flag_matches_serial_api(extra):
    """lgb.train with tree_learner=data: scatter on == scatter off ==
    serial at prediction tolerance, with NaNs in one column and an
    optional monotone constraint (both ride the sliced scan)."""
    rng = np.random.RandomState(11)
    n = 704
    X = rng.randn(n, 6)
    X[rng.rand(n) < 0.1, 3] = np.nan
    y = ((np.nan_to_num(X[:, 0]) + 0.5 * X[:, 1] -
          np.nan_to_num(X[:, 3]) * 0.3) > 0).astype(np.float64)
    p = {**SMALL, "objective": "binary", **extra}
    serial = lgb.train(p, lgb.Dataset(X, y), 4).predict(X)
    preds = {}
    for flag in (True, False):
        bst = lgb.train({**p, "tree_learner": "data",
                         "tpu_dp_hist_scatter": flag},
                        lgb.Dataset(X, y), 4)
        preds[flag] = bst.predict(X)
    np.testing.assert_allclose(preds[True], preds[False], atol=2e-6,
                               err_msg="scatter flag changed the model")
    np.testing.assert_allclose(preds[True], serial, atol=2e-5)


def test_dp_scatter_cat_and_forced_fall_back_to_psum():
    """Categorical shapes keep the full-batch psum under the flag (the
    static cat_idx subset search indexes full feature space) and still
    reproduce serial training; same for forced splits."""
    rng = np.random.RandomState(9)
    n = 640
    c = rng.randint(0, 8, n).astype(float)
    x1 = rng.randn(n)
    y = np.where(c % 2 == 0, 1.5, -1.5) + x1 * 0.3
    X = np.stack([c, x1], 1)
    p = {**SMALL, "objective": "regression", "cat_smooth": 1.0,
         "min_data_per_group": 1}
    preds = {}
    for tl in ("serial", "data"):
        bst = lgb.train({**p, "tree_learner": tl,
                         "tpu_dp_hist_scatter": True},
                        lgb.Dataset(X, y, categorical_feature=[0]), 4)
        preds[tl] = bst.predict(X)
    np.testing.assert_allclose(preds["data"], preds["serial"], atol=2e-5)

    import json
    import tempfile
    fs = {"feature": 0, "threshold": 0.0,
          "left": {"feature": 1, "threshold": 0.2}}
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as fh:
        json.dump(fs, fh)
        path = fh.name
    X2 = rng.randn(n, 4)
    y2 = (X2[:, 0] + 0.3 * X2[:, 1] > 0).astype(np.float64)
    pf = {**SMALL, "objective": "binary", "forcedsplits_filename": path,
          "tpu_dp_hist_scatter": True}
    want = lgb.train(pf, lgb.Dataset(X2, y2), 3).predict(X2)
    got = lgb.train({**pf, "tree_learner": "data"},
                    lgb.Dataset(X2, y2), 3).predict(X2)
    np.testing.assert_allclose(got, want, atol=2e-5)
