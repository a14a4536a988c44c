"""Speculative-ramp tests (learner/wave.py _spec_state).

The spec ramp grows a provisional subtree on a row subsample and commits
only splits verified against full-data channel histograms, so:
  (a) with the subsample == the full data, the grown tree must be
      IDENTICAL to the plain wave grower's (same splits, same numbering);
  (b) with a real (strided) subsample, misses may shrink the committed
      prefix but the result must stay a valid, learning tree — every
      recorded number is full-data exact by construction.
Both growers run the real Pallas kernels in interpret mode on CPU.
"""

import jax.numpy as jnp
import numpy as np

from lightgbm_tpu.learner.wave import make_wave_grow_fn
from lightgbm_tpu.ops.histogram_pallas import pad_rows
from lightgbm_tpu.ops.split import SplitParams


def _mk_data(n_raw=6000, f=6, b=64, seed=0):
    rng = np.random.RandomState(seed)
    n = pad_rows(n_raw)
    bins = rng.randint(0, b - 1, (f, n)).astype(np.uint8)
    # learnable structure over bin codes
    logit = (bins[0].astype(np.float32) / b - 0.5) * 3 + \
        ((bins[1] > 40).astype(np.float32) - 0.5) * 2
    y = (logit + rng.randn(n) * 0.7 > 0).astype(np.float32)
    p0 = 0.5
    grad = (p0 - y).astype(np.float32)
    hess = np.full(n, p0 * (1 - p0), np.float32)
    mask = np.ones(n, np.float32)
    mask[n_raw:] = 0.0
    return (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), y, n)


def _grow(spec, n, f=6, b=64, leaves=13, wave=4, quantized=False,
          spec_subsample=1 << 18):
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    return make_wave_grow_fn(
        num_leaves=leaves, num_features=f, max_bins=b, max_depth=0,
        split_params=sp, hist_impl="pallas", any_cat=False, interpret=True,
        jit=False, wave_size=wave, quantized=quantized, stochastic=False,
        spec_ramp=spec, spec_tol=0.02, spec_subsample=spec_subsample)


def _call(grow, bins, grad, hess, mask, f=6, b=64):
    nb = jnp.full((f,), b, jnp.int32)
    return grow(bins, grad, hess, mask, nb,
                jnp.zeros((f,), bool), jnp.zeros((f,), bool),
                jnp.zeros((f,), jnp.int32), jnp.zeros((f,), jnp.float32),
                (), jnp.ones((f,), bool))


def test_spec_full_subsample_matches_plain_exactly():
    bins, grad, hess, mask, y, n = _mk_data()
    t_plain = _call(_grow(False, n), bins, grad, hess, mask)
    t_spec = _call(_grow(True, n), bins, grad, hess, mask)
    assert int(t_spec.num_leaves) == int(t_plain.num_leaves)
    np.testing.assert_array_equal(np.asarray(t_spec.split_feature),
                                  np.asarray(t_plain.split_feature))
    np.testing.assert_array_equal(np.asarray(t_spec.threshold_bin),
                                  np.asarray(t_plain.threshold_bin))
    np.testing.assert_array_equal(np.asarray(t_spec.row_leaf),
                                  np.asarray(t_plain.row_leaf))
    np.testing.assert_allclose(np.asarray(t_spec.leaf_value),
                               np.asarray(t_plain.leaf_value),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(t_spec.split_gain),
                               np.asarray(t_plain.split_gain),
                               rtol=1e-4, atol=1e-4)


def test_spec_quantized_matches_plain():
    bins, grad, hess, mask, y, n = _mk_data(seed=3)
    t_plain = _call(_grow(False, n, quantized=True), bins, grad, hess, mask)
    t_spec = _call(_grow(True, n, quantized=True), bins, grad, hess, mask)
    assert int(t_spec.num_leaves) == int(t_plain.num_leaves)
    np.testing.assert_array_equal(np.asarray(t_spec.split_feature),
                                  np.asarray(t_plain.split_feature))
    np.testing.assert_array_equal(np.asarray(t_spec.row_leaf),
                                  np.asarray(t_plain.row_leaf))


def test_spec_strided_subsample_valid_tree():
    """Real subsampling (stride 2): commits may miss, but the tree must
    be structurally valid, full-data exact, and actually learn."""
    bins, grad, hess, mask, y, n = _mk_data(seed=7)
    t = _call(_grow(True, n, spec_subsample=4096), bins, grad, hess, mask)
    nl = int(t.num_leaves)
    assert 2 <= nl <= 13
    sf = np.asarray(t.split_feature)
    assert (sf >= 0).sum() == nl - 1
    # leaf counts: every live leaf obeys min_data_in_leaf; counts sum to n
    cnt = np.asarray(t.leaf_count)[:nl]
    assert cnt.min() >= 5
    assert cnt.sum() == float(np.asarray(mask).sum())
    # row_leaf consistent with leaf_count
    rl = np.asarray(t.row_leaf)
    m = np.asarray(mask) > 0
    bc = np.bincount(rl[m], minlength=13)
    np.testing.assert_array_equal(bc[:nl], cnt.astype(np.int64))
    # the pseudo-prediction from leaf values must beat the constant model
    lv = np.asarray(t.leaf_value)
    pred = 1.0 / (1.0 + np.exp(-4.0 * lv[rl]))  # lr-free monotone map
    base = -np.mean(y[m] * np.log(0.5) + (1 - y[m]) * np.log(0.5))
    p = np.clip(pred[m], 1e-6, 1 - 1e-6)
    ll = -np.mean(y[m] * np.log(p) + (1 - y[m]) * np.log(1 - p))
    assert ll < base


# ---------------------------------------------------------------------------
# Data-parallel speculative ramp (WaveDPStrategy.spec_ok): every shard
# strides its LOCAL rows and the provisional passes psum their histogram
# batches, so all shards grow one identical provisional tree verified
# against the full sharded data.  With stride 1 on both sides the serial
# and DP spec paths see identical pooled histograms, so the trees must
# match exactly (quantized: bit-for-bit — integer channel sums psum
# exactly).
# ---------------------------------------------------------------------------


def _mk_grow_dp(strategy, spec, wave=4, leaves=13, quantized=True):
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    return make_wave_grow_fn(
        num_leaves=leaves, num_features=6, max_bins=64, max_depth=0,
        split_params=sp, hist_impl="pallas", any_cat=False, interpret=True,
        jit=False, wave_size=wave, quantized=quantized, stochastic=False,
        spec_ramp=spec, spec_tol=0.02, strategy=strategy)


def _wrap_dp(grow, mesh, ax):
    from lightgbm_tpu.parallel.mesh import shard_wave_grower
    return shard_wave_grower(
        lambda X_T, g, h, m, nb, ic, hn, mono, cp, fm: grow(
            X_T, g, h, m, nb, ic, hn, mono, cp, (), fm), mesh, ax)


def test_spec_dp_matches_serial_on_mesh():
    """8-way row-sharded spec ramp == serial spec ramp, bit-for-bit on
    the quantized path (stride 1 both sides -> identical pooled
    histograms -> identical provisional trees and commits)."""
    from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy
    from lightgbm_tpu.parallel.mesh import get_mesh
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    bins, grad, hess, mask, y, n = _mk_data(n_raw=8 * 4096 - 100)
    assert n == 8 * 4096
    t_serial = _call(_mk_grow_dp(None, True), bins, grad, hess, mask)
    dp = _wrap_dp(_mk_grow_dp(WaveDPStrategy(ax, nshards=8), True),
                  mesh, ax)
    nb = jnp.full((6,), 64, jnp.int32)
    t_dp = dp(bins, grad, hess, mask, nb,
              jnp.zeros((6,), bool), jnp.zeros((6,), bool),
              jnp.zeros((6,), jnp.int32), jnp.zeros((6,), jnp.float32),
              jnp.ones((6,), bool))
    assert int(t_dp.num_leaves) == int(t_serial.num_leaves)
    for name in ("split_feature", "threshold_bin", "left_child",
                 "right_child", "decision_type"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_dp, name)),
            np.asarray(getattr(t_serial, name)), err_msg=name)
    np.testing.assert_array_equal(np.asarray(t_dp.row_leaf),
                                  np.asarray(t_serial.row_leaf))
    np.testing.assert_allclose(np.asarray(t_dp.leaf_value),
                               np.asarray(t_serial.leaf_value),
                               rtol=0, atol=1e-6)
    assert int(t_dp.hist_passes) == int(t_serial.hist_passes)


def test_spec_dp_one_psum_per_provisional_pass():
    """The DP spec ramp's only extra collectives are ONE histogram psum
    per provisional subsample pass (ceil(log2(W)) of them) — counted on
    the traced program: spec-on minus spec-off psum count == provisional
    passes + the verification mega-pass - the root pass it replaces."""
    import math
    from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy
    from lightgbm_tpu.parallel.mesh import get_mesh
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    bins, grad, hess, mask, y, n = _mk_data(n_raw=8 * 4096 - 100)
    nb = jnp.full((6,), 64, jnp.int32)
    args = (bins, grad, hess, mask, nb,
            jnp.zeros((6,), bool), jnp.zeros((6,), bool),
            jnp.zeros((6,), jnp.int32), jnp.zeros((6,), jnp.float32),
            jnp.ones((6,), bool))

    from lightgbm_tpu.analysis import ir

    def count_psums(spec):
        g = _wrap_dp(_mk_grow_dp(WaveDPStrategy(ax, nshards=8), spec),
                     mesh, ax)
        return ir.count_primitive(ir.trace(lambda *a: g(*a), *args), "psum")

    w = 4
    extra = count_psums(True) - count_psums(False)
    # spec-on adds ceil(log2(W)) provisional psums + 1 mega-pass psum and
    # drops the root-pass psum
    assert extra == math.ceil(math.log2(w)), extra
