"""Voting-parallel (PV-Tree, arXiv:1611.01276) histogram merging on the
wave grower (ISSUE 18 tentpole; learner/wave.py use_voting +
WaveVotingStrategy — the reference's VotingParallelTreeLearner
GlobalVoting/local-vote refinement, voting_parallel_tree_learner.cpp,
amortized over the wave's leaf batch).

Contract under test:
  * bit-identity — with 2k >= F the sorted global top-2k selection is
    the identity permutation, so the voted psum merges exactly the full
    histogram batch and the trained tree is IDENTICAL to the DP
    full-psum path and the serial grower (quantized path: bit-for-bit);
  * collective shape — the traced program holds one O(W*top_k) id
    all_gather per merge site and, at 2k < F, NO psum as large as a
    full (c, F, B, 3) histogram batch: every voted psum operand is at
    most (2k/F) of the full merge — the cross-host byte ratio the
    ISSUE's pod budget bounds;
  * typed config error — use_quantized_grad on the masked (non-wave)
    voting path raises QuantizedGradUnsupportedError instead of the old
    silent downgrade;
  * auto-selection — tree_learner=auto resolves to a concrete learner
    before training and records it in the model text.
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
from lightgbm_tpu.parallel.voting_parallel import (
    QuantizedGradUnsupportedError, WaveVotingStrategy, modeled_pass_bytes,
    voting_favored)

F, B, LEAVES, WAVE = 6, 64, 13, 4
NSH = 4            # shards: pallas row_block=4096 per shard bounds n


def _mk_data(seed=0):
    rng = np.random.RandomState(seed)
    n = NSH * 4096
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


def test_voting_matches_allreduce_and_serial_bitwise():
    """Quantized voting wave at top_k=3 (2k=6 >= F=6, identity
    selection): voting == full-psum DP == serial, bit-for-bit (endgame
    engages at 13 leaves / wave 4, so the shard-local bank and the
    winner exchange ride the vote too)."""
    mesh = get_mesh(NSH)
    ax = mesh.axis_names[0]
    data = _mk_data()
    args = data + _meta_args()
    t_ser = _serial_call(_mk_grow(None), data)
    t_ar = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=NSH)),
                    mesh, ax)(*args)
    t_vo = _wrap_dp(_mk_grow(WaveVotingStrategy(ax, nshards=NSH, top_k=3)),
                    mesh, ax)(*args)
    for name in BITWISE + ("split_gain", "leaf_value", "leaf_count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_vo, name)),
            np.asarray(getattr(t_ar, name)),
            err_msg=f"voting != allreduce: {name}")
    for name in BITWISE:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_vo, name)),
            np.asarray(getattr(t_ser, name)),
            err_msg=f"voting != serial: {name}")
    np.testing.assert_allclose(np.asarray(t_vo.leaf_value),
                               np.asarray(t_ser.leaf_value),
                               rtol=0, atol=1e-6)
    assert int(t_vo.hist_passes) == int(t_ser.hist_passes)


def test_voting_spec_ramp_rides_the_vote():
    """Spec ramp + voting: provisional subsample passes vote too, and
    the committed tree still equals serial spec growth bit-for-bit on
    the quantized path (2k >= F)."""
    mesh = get_mesh(NSH)
    ax = mesh.axis_names[0]
    data = _mk_data(seed=3)
    args = data + _meta_args()
    t_ser = _serial_call(_mk_grow(None, spec=True), data)
    t_vo = _wrap_dp(_mk_grow(WaveVotingStrategy(ax, nshards=NSH, top_k=3),
                             spec=True),
                    mesh, ax)(*args)
    for name in BITWISE:
        np.testing.assert_array_equal(
            np.asarray(getattr(t_vo, name)),
            np.asarray(getattr(t_ser, name)), err_msg=name)
    assert int(t_vo.hist_passes) == int(t_ser.hist_passes)


def test_voting_small_topk_still_grows():
    """top_k=1 (2k=2 < F=6): real feature filtering.  The tree is no
    longer guaranteed identical to serial, but it must be a valid full
    growth of the same size whose splits all hit voted features."""
    mesh = get_mesh(NSH)
    ax = mesh.axis_names[0]
    data = _mk_data(seed=5)
    args = data + _meta_args()
    t = _wrap_dp(_mk_grow(WaveVotingStrategy(ax, nshards=NSH, top_k=1)),
                 mesh, ax)(*args)
    assert int(t.num_leaves) == LEAVES
    sf = np.asarray(t.split_feature)[:int(t.num_leaves) - 1]
    assert ((sf >= 0) & (sf < F)).all()


# ---------------------------------------------------------------------------
# Traced-program shape: the vote's id all_gather per merge site and the
# voted psum never as large as the full histogram batch at 2k < F.
# ---------------------------------------------------------------------------

from lightgbm_tpu.analysis.ir import collect_collectives as _collectives_of


def test_voting_traced_collectives_shape():
    """At top_k=1 the voted psum operand is (c, 2, B, 3) against the
    allreduce baseline's (c, F, B, 3): per-leaf bytes ratio == 2k/F —
    the ISSUE's cross-host budget — and an all_gather per merge site
    carries the O(W*k) ids."""
    mesh = get_mesh(NSH)
    ax = mesh.axis_names[0]
    args = _mk_data() + _meta_args()
    g_vo = _wrap_dp(_mk_grow(WaveVotingStrategy(ax, nshards=NSH, top_k=1)),
                    mesh, ax)
    g_ar = _wrap_dp(_mk_grow(WaveDPStrategy(ax, nshards=NSH)), mesh, ax)
    coll_vo = _collectives_of(lambda *a: g_vo(*a), *args)
    coll_ar = _collectives_of(lambda *a: g_ar(*a), *args)

    ag_names = [k for k in coll_vo if "all_gather" in k]
    assert ag_names, f"no all_gather traced: {sorted(coll_vo)}"
    # one id gather per histogram-merge site (root + body + endgame)
    n_ag = sum(len(coll_vo[k]) for k in ag_names)
    assert n_ag == 3, (n_ag, coll_vo)
    assert not any("all_gather" in k for k in coll_ar), coll_ar

    # full hist batch per leaf: F*B*3; voted: min(2k,F)*B*3 = 2*B*3
    full_leaf = F * B * 3
    voted_leaf = 2 * B * 3
    big_ar = [s for s in coll_ar.get("psum", []) if s >= WAVE * full_leaf]
    assert big_ar, "allreduce baseline lost its histogram psum?"
    # the voting program's biggest psum is the voted batch — per-leaf
    # exactly (2k/F) of the full merge, never a full-F histogram
    vo_psums = coll_vo.get("psum", [])
    assert vo_psums
    assert max(vo_psums) <= max(2 * WAVE, LEAVES) * voted_leaf, vo_psums
    assert not [s for s in vo_psums if s >= WAVE * full_leaf], vo_psums


def test_modeled_pass_bytes_ratio_and_auto_rule():
    """The byte model the auto-selection + CI artifact share: voting's
    total undercuts reduce-scatter once F is wide, ratio == 2k/F, and
    voting_favored flips on exactly when modeled cross-host bytes drop
    below the DP path's (and never below the world-size floor)."""
    m = modeled_pass_bytes(num_features=512, bins=64, top_k=16, world=64)
    assert m["hosts"] == 8
    assert m["voted_full_ratio"] == pytest.approx(32 / 512)
    assert m["voting"]["cross_host"] < m["reduce_scatter"]["cross_host"]
    assert voting_favored(512, 64, 16, 64)
    # narrow F: the vote's id gather overhead loses
    assert not voting_favored(4, 64, 20, 64)
    # below the world floor voting never engages
    assert not voting_favored(512, 64, 16, 2)


# ---------------------------------------------------------------------------
# Public API: tree_learner=voting parity, typed quantized error, auto
# ---------------------------------------------------------------------------

SMALL = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1,
         "tree_grow_mode": "wave"}


def test_voting_api_matches_data_quantized():
    """lgb.train with tree_learner=voting (wave path, default top_k=20
    >= F so selection is identity) against tree_learner=data on the
    quantized path: the sharded learners must agree (stochastic rounding
    folds the shard index into the key, so they agree with EACH OTHER
    exactly, not with unsharded serial rounding — float voting-vs-serial
    parity is proven bitwise at grower level above)."""
    rng = np.random.RandomState(11)
    n = 704
    X = rng.randn(n, 6)
    y = ((X[:, 0] + 0.5 * X[:, 1]) > 0).astype(np.float64)
    pq = {**SMALL, "objective": "binary", "use_quantized_grad": True}
    dp_q = lgb.train({**pq, "tree_learner": "data"},
                     lgb.Dataset(X, y), 4).predict(X)
    vo_q = lgb.train({**pq, "tree_learner": "voting"},
                     lgb.Dataset(X, y), 4).predict(X)
    np.testing.assert_allclose(vo_q, dp_q, atol=2e-6,
                               err_msg="voting != data (quantized)")


def test_voting_quantized_masked_path_raises_typed():
    """use_quantized_grad on the masked (partition-mode) voting path:
    loud typed error, not the old silent downgrade."""
    rng = np.random.RandomState(7)
    X = rng.randn(256, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    p = {**SMALL, "objective": "binary", "tree_learner": "voting",
         "tree_grow_mode": "partition", "use_quantized_grad": True}
    with pytest.raises(QuantizedGradUnsupportedError):
        lgb.train(p, lgb.Dataset(X, y), 2)


def test_tree_learner_auto_resolves_and_records():
    """tree_learner=auto trains and the model text records the RESOLVED
    learner (never the literal 'auto')."""
    rng = np.random.RandomState(3)
    X = rng.randn(512, 6)
    y = (X[:, 0] - X[:, 2] > 0).astype(np.float64)
    p = {**SMALL, "objective": "binary", "tree_learner": "auto"}
    bst = lgb.train(p, lgb.Dataset(X, y), 3)
    txt = bst.model_to_string()
    line = [ln for ln in txt.splitlines()
            if ln.startswith("[tree_learner:")]
    assert line and "auto" not in line[0], line
    serial = lgb.train({**SMALL, "objective": "binary"},
                       lgb.Dataset(X, y), 3).predict(X)
    np.testing.assert_allclose(bst.predict(X), serial, atol=2e-5)
