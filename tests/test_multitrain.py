"""One-program multi-model training (lightgbm_tpu.multitrain, ISSUE 7).

The load-bearing contract: model m of a ``train_many`` batch is
BIT-identical (model text + predictions) to the booster a standalone
``train(variants[m])`` with the same seeds produces — on the partition
and wave growers, quantized on/off, with bagging / feature_fraction /
balanced bagging / early stopping active — while all M models share one
binned dataset and ONE compiled grower program.
"""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import ManyBooster, MultiTrainError, train_many
from lightgbm_tpu.multitrain.batched import BatchTrainer, batch_reject_reason
from lightgbm_tpu.multitrain.variants import (HOST_SWEEP, TRACED_SWEEP,
                                              group_variants,
                                              normalize_variants,
                                              structure_key)
from lightgbm_tpu.utils.random import host_rng, model_stream_seed

BASE = {"objective": "regression", "num_leaves": 15, "learning_rate": 0.1,
        "min_data_in_leaf": 5, "verbosity": -1}
N, F = 1200, 8


def _data(seed=0, n=N, f=F):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X[:, 0] * 2 + np.sin(X[:, 1] * 3) + 0.1 * rng.randn(n)
    return X, y


def _fit_ref(params, X, y, rounds, valid=None):
    ds = lgb.Dataset(X, y)
    kw = {}
    if valid is not None:
        kw = dict(valid_sets=[lgb.Dataset(valid[0], valid[1], reference=ds)],
                  valid_names=["v0"])
    return lgb.train(params, ds, rounds, **kw)


def _assert_bit_identical(mb, vparams, X, y, rounds, valid=None):
    for m, v in enumerate(vparams):
        ref = _fit_ref({**BASE, **v}, X, y, rounds, valid)
        assert ref.model_to_string() == mb[m].model_to_string(), \
            f"model {m} ({v}) text differs from standalone train()"
        assert np.array_equal(ref.predict(X[:64]), mb[m].predict(X[:64]))
        assert ref.best_iteration == mb[m].best_iteration


# -- bit-identity vs the sequential loop ------------------------------------

@pytest.mark.parametrize("mode_params", [
    {},                                       # partition grower
    {"tree_grow_mode": "wave", "tpu_wave_size": 4},   # wave grower
    pytest.param({"use_quantized_grad": True},
                 marks=pytest.mark.slow),     # quantized (exact fallback)
    pytest.param({"tree_grow_mode": "wave", "tpu_wave_size": 4,
                  "use_quantized_grad": True},
                 marks=pytest.mark.slow),     # true int8 quantized wave
], ids=["partition", "wave", "quantized", "wave-quantized"])
def test_bit_identity_sweep(mode_params):
    X, y = _data()
    variants = [{"lambda_l1": 0.0}, {"lambda_l1": 0.7, "lambda_l2": 2.0},
                {"min_data_in_leaf": 20}]
    params = {**BASE, **mode_params}
    mb = train_many(params, lgb.Dataset(X, y), num_boost_round=5,
                    variants=variants)
    assert mb.fallback_indices == []
    _assert_bit_identical(mb, [{**mode_params, **v} for v in variants],
                          X, y, 5)


def test_bit_identity_pallas_wave():
    """ISSUE 8: the vmap gate is lifted — batched training rides the
    Pallas histogram kernels (interpret-mode off TPU) through jax's
    pallas_call batching rule, bit-identical per model to a standalone
    pallas train().  Small geometry: the interpret kernels are a
    correctness proxy, not a speed path, on this env."""
    X, y = _data()
    params = {**BASE, "num_leaves": 7, "tree_grow_mode": "wave",
              "tpu_wave_size": 2, "tpu_histogram_impl": "pallas",
              "tpu_speculative_ramp": False}
    variants = [{"lambda_l2": 0.0}, {"lambda_l2": 2.0}]
    mb = train_many(params, lgb.Dataset(X, y), num_boost_round=2,
                    variants=variants)
    assert mb.fallback_indices == []
    base = {k: v for k, v in params.items() if k not in BASE or k in
            ("num_leaves",)}
    _assert_bit_identical(mb, [{**base, **v} for v in variants], X, y, 2)


def test_bit_identity_bagging_and_feature_fraction():
    """The per-model RNG satellite: the batch's host-side bagging and
    feature_fraction draws must be the standalone draws, per model."""
    X, y = _data()
    params = {**BASE, "bagging_fraction": 0.7, "bagging_freq": 2,
              "feature_fraction": 0.6, "seed": 3}
    variants = [{}, {"bagging_seed": 99}, {"feature_fraction_seed": 17}]
    mb = train_many(params, lgb.Dataset(X, y), num_boost_round=6,
                    variants=variants)
    base_nofold = {k: v for k, v in params.items() if k not in BASE}
    _assert_bit_identical(mb, [{**base_nofold, **v} for v in variants],
                          X, y, 6)


@pytest.mark.slow
def test_bit_identity_balanced_bagging_binary():
    X, y = _data()
    yb = (y > 0).astype(np.float64)
    params = {**BASE, "objective": "binary", "pos_bagging_fraction": 0.8,
              "neg_bagging_fraction": 0.5, "bagging_freq": 1}
    mb = train_many(params, lgb.Dataset(X, yb), num_boost_round=5)
    ref = lgb.train(params, lgb.Dataset(X, yb), 5)
    assert ref.model_to_string() == mb[0].model_to_string()


def test_masked_early_stopping_each_model_stops_at_its_own_round():
    X, y = _data()
    Xv, yv = _data(seed=1, n=400)
    variants = [{"learning_rate": 0.5}, {"learning_rate": 0.1}]
    params = {**BASE, "early_stopping_round": 3}
    ds = lgb.Dataset(X, y)
    mb = train_many(params, ds, num_boost_round=30, variants=variants,
                    valid_sets=[lgb.Dataset(Xv, yv, reference=ds)],
                    valid_names=["v0"])
    refs = [_fit_ref({**params, **v}, X, y, 30, valid=(Xv, yv))
            for v in variants]
    for m, ref in enumerate(refs):
        assert mb[m].best_iteration == ref.best_iteration
        assert ref.model_to_string() == mb[m].model_to_string()
    # the fast model stops earlier than the slow one — genuinely
    # per-model stopping, not a shared round
    assert mb.best_iteration[0] != mb.best_iteration[1]
    # eval history matches the standalone early-stop run's metric keys
    assert "v0" in mb.eval_histories[0]


def test_bit_identity_pmap_sharded_model_axis():
    """M divisible by the device count engages the pmap-sharded model
    axis (each device grows M/k models); per-lane values are unchanged,
    so every extracted model stays bit-identical to standalone."""
    import jax
    if jax.local_device_count() < 2:
        pytest.skip("needs the multi-device CPU mesh")
    X, y = _data(n=800)
    k = jax.local_device_count()
    variants = [{"lambda_l1": 0.1 * i} for i in range(k)]
    tr = BatchTrainer([{**BASE, **v} for v in variants], lgb.Dataset(X, y))
    assert tr._shard, "M == device count must shard the model axis"
    mb = train_many(BASE, lgb.Dataset(X, y), num_boost_round=4,
                    variants=variants)
    _assert_bit_identical(mb, variants, X, y, 4)


# -- PR-20 lifted variants: GOSS / DART / multiclass / ranking ---------------

def _mc_data(seed=0, n=N, f=F):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    raw = X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n)
    return X, np.digitize(raw, [-0.5, 0.5]).astype(np.float64)


def _rank_data(seed=0, n=N, f=F, gsize=30):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    rel = np.clip((X[:, 0] + 0.5 * rng.randn(n)) + 2, 0, 4).astype(int)
    groups = [gsize] * (n // gsize)
    groups[-1] += n - sum(groups)
    return X, rel.astype(np.float64), groups


@pytest.mark.slow
def test_bit_identity_goss_batch():
    """GOSS batches (PR 20): the per-lane draw is the one jitted sampler
    (goss_sample_np, its host face), so every lane's thinning equals its
    standalone run — top/other rates sweep host-side in one batch."""
    X, y = _data()
    params = {**BASE, "boosting": "goss", "learning_rate": 0.5}
    variants = [{"top_rate": 0.2, "other_rate": 0.1},
                {"top_rate": 0.3, "other_rate": 0.2},
                {"top_rate": 0.2, "other_rate": 0.1, "lambda_l1": 0.5}]
    mb = train_many(params, lgb.Dataset(X, y), num_boost_round=6,
                    variants=variants)
    assert mb.fallback_indices == []
    assert mb.num_groups == 1, "goss rate sweeps must share one batch"
    base = {"boosting": "goss", "learning_rate": 0.5}
    _assert_bit_identical(mb, [{**base, **v} for v in variants], X, y, 6)


@pytest.mark.slow
def test_bit_identity_dart_batch():
    """DART batches (PR 20): per-lane drop sets from the standalone
    (drop_seed, iteration) streams, Normalize as lane-masked axpys —
    drop knobs sweep host-side in one batch."""
    X, y = _data()
    params = {**BASE, "boosting": "dart"}
    variants = [{"drop_rate": 0.3, "drop_seed": 9},
                {"drop_rate": 0.6, "drop_seed": 9},
                {"drop_rate": 0.3, "drop_seed": 4,
                 "xgboost_dart_mode": True}]
    mb = train_many(params, lgb.Dataset(X, y), num_boost_round=7,
                    variants=variants)
    assert mb.fallback_indices == []
    assert mb.num_groups == 1, "dart drop sweeps must share one batch"
    _assert_bit_identical(mb, [{"boosting": "dart", **v}
                               for v in variants], X, y, 7)


@pytest.mark.slow
def test_bit_identity_multiclass_batch():
    """Multiclass batches (PR 20) as an (M, K) lane grid; composed with
    bagging + feature_fraction the per-lane draws still equal the
    standalone per-class streams."""
    X, y = _mc_data()
    params = {**BASE, "objective": "multiclass", "num_class": 3,
              "bagging_fraction": 0.7, "bagging_freq": 2,
              "feature_fraction": 0.8}
    variants = [{"lambda_l2": 0.0}, {"lambda_l2": 3.0},
                {"bagging_seed": 99}]
    mb = train_many(params, lgb.Dataset(X, y), num_boost_round=5,
                    variants=variants)
    assert mb.fallback_indices == []
    base = {k: v for k, v in params.items()
            if k not in BASE or k == "objective"}
    base["objective"] = "multiclass"
    _assert_bit_identical(mb, [{**base, **v} for v in variants], X, y, 5)


@pytest.mark.slow
def test_bit_identity_multiclass_early_stopping():
    X, y = _mc_data()
    Xv, yv = _mc_data(seed=1, n=400)
    params = {**BASE, "objective": "multiclass", "num_class": 3,
              "early_stopping_round": 3}
    variants = [{"learning_rate": 0.5}, {"learning_rate": 0.05}]
    ds = lgb.Dataset(X, y)
    mb = train_many(params, ds, num_boost_round=25, variants=variants,
                    valid_sets=[lgb.Dataset(Xv, yv, reference=ds)],
                    valid_names=["v0"])
    for m, v in enumerate(variants):
        p = {"objective": "multiclass", "num_class": 3,
             "early_stopping_round": 3, **v}
        ref = _fit_ref({**BASE, **p}, X, y, 25, valid=(Xv, yv))
        assert mb[m].best_iteration == ref.best_iteration
        assert ref.model_to_string() == mb[m].model_to_string()


@pytest.mark.slow
def test_ranking_structure_and_f32_parity():
    """Ranking batches (PR 20): the per-group lambdarank pass is
    lane-masked; trees match the standalone run structurally and
    predictions agree to f32 tolerance (the batched gradient pass
    reduces over the padded group axis in a different order)."""
    X, y, groups = _rank_data()
    params = {**BASE, "objective": "lambdarank",
              "metric": "ndcg", "ndcg_eval_at": [5]}
    variants = [{"lambda_l2": 0.0}, {"lambda_l2": 2.0}]
    mb = train_many(params, lgb.Dataset(X, y, group=groups),
                    num_boost_round=5, variants=variants)
    assert mb.fallback_indices == []
    for m, v in enumerate(variants):
        p = {**BASE, "objective": "lambdarank", "metric": "ndcg",
             "ndcg_eval_at": [5], **v}
        ref = lgb.train(p, lgb.Dataset(X, y, group=groups), 5)
        s_ref = [(t.split_feature.tolist(), t.threshold_bin.tolist())
                 for t in ref._gbdt.models]
        s_bat = [(t.split_feature.tolist(), t.threshold_bin.tolist())
                 for t in mb[m]._gbdt.models]
        assert s_ref == s_bat, f"ranking model {m} tree structure differs"
        np.testing.assert_allclose(ref.predict(X[:128]),
                                   mb[m].predict(X[:128]),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("lift", [
    {"boosting": "goss", "learning_rate": 0.5},
    {"boosting": "dart", "drop_rate": 0.4, "drop_seed": 9},
    {"objective": "multiclass", "num_class": 3},
], ids=["goss", "dart", "multiclass"])
def test_cv_fold_parity_lifted(lift):
    """engine.cv routes the lifted variants through the batched fold
    driver; masked folds agree with the legacy compacted-subset loop to
    f32 reduction tolerance (multiclass amplifies via softmax -> wider
    rtol, same bar as the masked-subset parity test)."""
    if lift.get("objective") == "multiclass":
        X, y = _mc_data()
    else:
        X, y = _data()
    P = {**BASE, **lift}
    kw = dict(num_boost_round=5, nfold=3, seed=7)
    fast = lgb.cv(P, lgb.Dataset(X, y), **kw)
    slow = lgb.cv({**P, "tpu_cv_many": False}, lgb.Dataset(X, y), **kw)
    assert sorted(fast) == sorted(slow)
    for k in fast:
        np.testing.assert_allclose(fast[k], slow[k], rtol=2e-4, atol=1e-6,
                                   err_msg=k)


# -- one compile for M models ------------------------------------------------

def test_one_compile_for_m_models():
    """M models, ONE compiled grower program: the batch's jitted vmapped
    grower has exactly one executable in its cache after training, and
    growing the batch twice as wide reuses the same BatchTrainer program
    shape count (no per-model retrace)."""
    X, y = _data(n=600)
    variants = [{"lambda_l1": float(v)} for v in (0.0, 0.3, 0.9, 2.7)]
    tr = BatchTrainer([{**BASE, **v} for v in variants],
                      lgb.Dataset(X, y))
    tr.run(4)
    assert tr._vm_grow._cache_size() == 1, \
        "M models must share ONE compiled grower program"
    tr.finalize()


def test_traced_sweep_shares_structure_key():
    vs = normalize_variants(BASE, [{"lambda_l1": 0.1},
                                   {"lambda_l2": 5.0},
                                   {"learning_rate": 0.3},
                                   {"num_leaves": 31}])
    groups = group_variants(vs)
    # lambda/lr sweeps share a structure; num_leaves forces a new one
    assert groups == [[0, 1, 2], [3]]
    assert structure_key(vs[0]) == structure_key(vs[1])
    assert structure_key(vs[0]) != structure_key(vs[3])
    for f in ("lambda_l1", "lambda_l2", "min_sum_hessian_in_leaf",
              "min_data_in_leaf", "min_gain_to_split"):
        assert f in TRACED_SWEEP
    assert "learning_rate" in HOST_SWEEP


def test_structural_group_fallback_trains_everything():
    X, y = _data(n=600)
    variants = [{"lambda_l1": 0.5}, {"num_leaves": 7},
                {"cegb_penalty_split": 0.1}]  # CEGB cannot batch -> fallback
    mb = train_many(BASE, lgb.Dataset(X, y), num_boost_round=3,
                    variants=variants)
    assert sorted(mb.batched_indices) == [0, 1]
    assert mb.fallback_indices == [2]
    assert all(b is not None for b in mb.boosters)
    _assert_bit_identical(mb, variants[:2], X, y, 3)


def test_replicas_derive_decorrelated_seeds():
    X, y = _data(n=600)
    params = {**BASE, "bagging_fraction": 0.6, "bagging_freq": 1,
              "seed": 11, "bagging_seed": 5}
    mb = train_many(params, lgb.Dataset(X, y), num_boost_round=3,
                    replicas=3)
    # derived seeds are a pure function of (seed, model) and are
    # materialized into variant_params -> standalone reproducible.
    # model 0 keeps the base master seed (Config cascades sub-seeds
    # from a nonzero seed, so the master seed is what decorrelates)
    assert mb.variant_params[0]["seed"] == 11
    assert mb.variant_params[1]["seed"] == model_stream_seed(11, 1)
    assert mb.variant_params[1] != mb.variant_params[2]
    texts = {b.model_to_string() for b in mb}
    assert len(texts) == 3, "replicas must train decorrelated models"
    ref = lgb.train(mb.variant_params[2], lgb.Dataset(X, y), 3)
    assert ref.model_to_string() == mb[2].model_to_string()


def test_model_zero_keys_historical_stream():
    """model=0 must key Philox exactly like the historical 1-word form —
    every pre-existing single-model stream is unchanged."""
    a = host_rng(1234, 7).integers(0, 1 << 30, 16)
    b = host_rng(1234, 7, model=0).integers(0, 1 << 30, 16)
    assert np.array_equal(a, b)
    c = host_rng(1234, 7, model=1).integers(0, 1 << 30, 16)
    assert not np.array_equal(a, c)


# -- ManyBooster surface ------------------------------------------------------

def test_many_booster_container():
    X, y = _data(n=600)
    mb = train_many(BASE, lgb.Dataset(X, y), num_boost_round=3,
                    variants=[{"lambda_l1": v} for v in (0.0, 1.0)])
    assert isinstance(mb, ManyBooster)
    assert len(mb) == 2 and len(list(mb)) == 2
    stack = mb.predict(X[:32])
    assert stack.shape == (2, 32)
    assert np.array_equal(stack[1], mb[1].predict(X[:32]))


def test_sample_masks_against_shared_dataset():
    X, y = _data()
    rows0 = np.arange(0, N, 2)
    rows1 = np.arange(0, N, 3)
    masks = np.zeros((2, N), np.float32)
    masks[0, rows0] = 1.0
    masks[1, rows1] = 1.0
    mb = train_many(BASE, lgb.Dataset(X, y), num_boost_round=4,
                    sample_masks=masks)
    # each masked model only ever saw its rows: retraining standalone on
    # the SAME binned view (subset shares the parent's bin mappers)
    # gives a model whose predictions agree to f32 reduction tolerance
    parent = lgb.Dataset(X, y)
    parent.construct(lgb.Config(BASE))
    sub = parent.subset(rows0)
    assert sub.bin_mappers is parent.bin_mappers, \
        "folds must share the parent's bin mappers (binning done once)"
    ref = lgb.train(BASE, sub, 4)
    p1, p2 = ref.predict(X[:200]), mb[0].predict(X[:200])
    np.testing.assert_allclose(p1, p2, rtol=2e-4, atol=2e-5)


# -- engine.cv fast path ------------------------------------------------------

def test_cv_through_train_many_matches_fold_loop():
    X, y = _data()
    ds_kwargs = dict(num_boost_round=6, nfold=3, seed=7)
    fast = lgb.cv(BASE, lgb.Dataset(X, y), **ds_kwargs)
    slow = lgb.cv({**BASE, "tpu_cv_many": False}, lgb.Dataset(X, y),
                  **ds_kwargs)
    assert sorted(fast) == sorted(slow)
    for k in fast:
        np.testing.assert_allclose(fast[k], slow[k], rtol=5e-5, atol=1e-7,
                                   err_msg=k)


def test_cv_early_stopping_parity_and_cvbooster():
    X, y = _data()
    P = {**BASE, "early_stopping_round": 3, "learning_rate": 0.5}
    kw = dict(num_boost_round=35, nfold=3, seed=7, return_cvbooster=True)
    fast = lgb.cv(P, lgb.Dataset(X, y), **kw)
    slow = lgb.cv({**P, "tpu_cv_many": False}, lgb.Dataset(X, y), **kw)
    assert len(fast["valid l2-mean"]) == len(slow["valid l2-mean"])
    assert fast["cvbooster"].best_iteration == \
        slow["cvbooster"].best_iteration
    assert len(fast["cvbooster"].boosters) == 3
    # extracted fold boosters predict
    p = fast["cvbooster"].boosters[0].predict(X[:16])
    assert p.shape == (16,)


def test_cv_eval_train_metric_and_custom_folds():
    X, y = _data(n=800)
    folds = [(np.arange(0, 800, 2), np.arange(1, 800, 2)),
             (np.arange(1, 800, 2), np.arange(0, 800, 2))]
    fast = lgb.cv(BASE, lgb.Dataset(X, y), num_boost_round=4, folds=folds,
                  eval_train_metric=True)
    slow = lgb.cv({**BASE, "tpu_cv_many": False}, lgb.Dataset(X, y),
                  num_boost_round=4, folds=folds, eval_train_metric=True)
    assert sorted(fast) == sorted(slow)
    assert "train l2-mean" in fast
    for k in fast:
        np.testing.assert_allclose(fast[k], slow[k], rtol=5e-5, atol=1e-7,
                                   err_msg=k)


def test_cv_falls_back_on_custom_feval():
    X, y = _data(n=600)
    calls = []

    def feval(preds, ds):
        calls.append(1)
        return "dummy", 0.0, False

    out = lgb.cv(BASE, lgb.Dataset(X, y), num_boost_round=2, nfold=2,
                 feval=feval)
    assert calls, "custom feval must run (legacy path)"
    assert "valid dummy-mean" in out


# -- rejection / fallback edges ----------------------------------------------

def test_reject_reasons():
    X, y = _data(n=400)
    ds = lgb.Dataset(X, y)
    ds.construct(lgb.Config(BASE))
    assert batch_reject_reason(lgb.Config(BASE), ds) is None
    # the PR-20 lifts: goss / dart / multiclass / ranking all batch now
    for lifted in ({"boosting": "goss"}, {"boosting": "dart"},
                   {"objective": "multiclass", "num_class": 3},
                   {"objective": "lambdarank"}):
        assert batch_reject_reason(lgb.Config({**BASE, **lifted}), ds) \
            is None, f"{lifted} must no longer reject"
    # every REMAINING reject string, hit explicitly (coverage: a new
    # reject added without a test here is a lint failure by convention)
    assert "tree_learner" in batch_reject_reason(
        lgb.Config({**BASE, "tree_learner": "data"}), ds)
    assert "boosting=rf" in batch_reject_reason(
        lgb.Config({**BASE, "boosting": "rf", "bagging_freq": 1,
                    "bagging_fraction": 0.5}), ds)
    assert "objective=none" in batch_reject_reason(
        lgb.Config({**BASE, "objective": "none"}), ds)
    assert "linear_tree" in batch_reject_reason(
        lgb.Config({**BASE, "linear_tree": True}), ds)
    assert "CEGB" in batch_reject_reason(
        lgb.Config({**BASE, "cegb_penalty_split": 0.1}), ds)


def test_strict_mode_and_fallback_counter():
    """The never-silent contract: strict=True raises instead of going
    sequential, and EVERY fallback bumps
    multitrain_fallback_total{reason} with the bounded reason prefix."""
    from lightgbm_tpu.telemetry.metrics import default_registry
    X, y = _data(n=400)
    with pytest.raises(MultiTrainError, match="CEGB"):
        train_many({**BASE, "cegb_penalty_split": 0.1}, lgb.Dataset(X, y),
                   num_boost_round=2, strict=True)
    reg = default_registry()
    ctr = reg.counter("multitrain_fallback_total",
                      "train_many models that fell back to sequential "
                      "train(), by structural reason", labels=("reason",))
    c0 = ctr.value(reason="CEGB penalties")
    train_many({**BASE, "cegb_penalty_split": 0.1}, lgb.Dataset(X, y),
               num_boost_round=2)
    # bounded label: the free text after " (" is stripped
    assert ctr.value(reason="CEGB penalties") == c0 + 1
    req = reg.counter("multitrain_models_requested_total",
                      "models requested through train_many "
                      "(batched or not)")
    assert req.value() >= 2


def test_fallback_rate_slo_declared_and_covered():
    """The multitrain/fallback_rate SLO keys to registered series (the
    slo_cover lint runs this fleet-wide; asserted here so the contract
    is local to the subsystem too)."""
    from lightgbm_tpu.analysis.slo_cover import check_slo_coverage
    from lightgbm_tpu.telemetry.slo import all_slos
    assert "multitrain/fallback_rate" in all_slos()
    bad = [v for v in check_slo_coverage()
           if "multitrain" in v.site]
    assert bad == []


def test_masked_is_unbalance_rejected():
    """is_unbalance derives label_weight from the FULL dataset's pos/neg
    counts; a fold-masked model's standalone counterpart derives it from
    its own rows — must reject, and cv() must fall back to the legacy
    fold loop (which subsets per fold and reweights correctly)."""
    X, y = _data(n=600)
    yb = (y > 0).astype(np.float64)
    masks = np.ones((2, 600), np.float32)
    masks[0, ::3] = 0.0
    with pytest.raises(MultiTrainError, match="is_unbalance"):
        BatchTrainer([{**BASE, "objective": "binary",
                       "is_unbalance": True}] * 2,
                     lgb.Dataset(X, yb), sample_masks=masks)
    # unmasked batches share the full metadata with their standalone
    # counterparts, so is_unbalance stays batchable there
    out = lgb.cv({**BASE, "objective": "binary", "is_unbalance": True},
                 lgb.Dataset(X, yb), num_boost_round=2, nfold=2)
    assert len(out["valid binary_logloss-mean"]) == 2


def test_allow_fallback_false_raises():
    X, y = _data(n=400)
    with pytest.raises(MultiTrainError):
        train_many({**BASE, "cegb_penalty_split": 0.1}, lgb.Dataset(X, y),
                   num_boost_round=2, allow_fallback=False)


def test_variant_columns_and_length_mismatch():
    vs = normalize_variants(BASE, {"lambda_l1": [0.0, 1.0],
                                   "learning_rate": [0.1, 0.2]})
    assert len(vs) == 2 and vs[1]["lambda_l1"] == 1.0
    with pytest.raises(ValueError):
        normalize_variants(BASE, {"lambda_l1": [0.0, 1.0],
                                  "learning_rate": [0.1]})
    with pytest.raises(ValueError):
        normalize_variants(BASE, [{}], replicas=2)


# -- checkpoint interop (chaos) ----------------------------------------------

@pytest.mark.chaos
def test_train_many_rejects_checkpointing(tmp_path):
    """Never a silent bad resume: checkpoint/resume params raise a typed
    CheckpointError in train_many instead of training without the fault
    tolerance they asked for."""
    from lightgbm_tpu import CheckpointError
    X, y = _data(n=400)
    for bad in ({"checkpoint_dir": str(tmp_path)},
                {"snapshot_freq": 2},
                {"resume": "latest"}):
        with pytest.raises(CheckpointError, match="train_many"):
            train_many({**BASE, **bad}, lgb.Dataset(X, y),
                       num_boost_round=2)


@pytest.mark.chaos
def test_cv_with_checkpoint_params_falls_back_to_fold_loop(tmp_path):
    """engine.cv never checkpointed; with checkpoint params present the
    fast path steps aside and the legacy loop runs unchanged."""
    X, y = _data(n=400)
    out = lgb.cv({**BASE, "snapshot_freq": 2}, lgb.Dataset(X, y),
                 num_boost_round=2, nfold=2)
    assert "valid l2-mean" in out and len(out["valid l2-mean"]) == 2


@pytest.mark.chaos
def test_train_many_fault_injection_propagates():
    from lightgbm_tpu.resilience.faults import InjectedFault, faults
    X, y = _data(n=400)
    faults.clear()
    try:
        faults.configure("crash_at_iter=1")
        with pytest.raises(InjectedFault):
            train_many(BASE, lgb.Dataset(X, y), num_boost_round=4)
    finally:
        faults.clear()


# -- telemetry ----------------------------------------------------------------

def test_telemetry_counters_and_train_record():
    from lightgbm_tpu.telemetry.metrics import default_registry
    X, y = _data(n=400)
    reg = default_registry()
    c0 = reg.counter("multitrain_models_total",
                     "models trained on the vmapped model axis").value()
    mb = train_many(BASE, lgb.Dataset(X, y), num_boost_round=3,
                    variants=[{"lambda_l1": v} for v in (0.0, 1.0, 2.0)])
    c1 = reg.counter("multitrain_models_total",
                     "models trained on the vmapped model axis").value()
    assert c1 - c0 == 3
    # per-model TrainRecords surface through the extracted boosters
    rec = mb[1].train_record
    assert rec.meta["multitrain_model_index"] == 1
    assert rec.meta["multitrain_models"] == 3
    assert rec.snapshot()["num_trees"] == 3


# -- sklearn sweep ------------------------------------------------------------

def test_grid_search_cv_many_regressor():
    pytest.importorskip("sklearn")
    from lightgbm_tpu.multitrain import GridSearchCVMany
    from lightgbm_tpu.sklearn import LGBMRegressor
    X, y = _data(n=800)
    gs = GridSearchCVMany(
        LGBMRegressor(n_estimators=8, num_leaves=15, min_child_samples=5),
        {"reg_lambda": [0.0, 1.0], "learning_rate": [0.1, 0.3]}, cv=3)
    gs.fit(X, y)
    assert len(gs.cv_results_["params"]) == 4
    assert gs.cv_results_["mean_test_score"].shape == (4,)
    assert set(gs.best_params_) == {"reg_lambda", "learning_rate"}
    assert gs.best_score_ == max(gs.cv_results_["mean_test_score"])
    assert 1 in gs.cv_results_["rank_test_score"]
    # refit estimator predicts on full data
    assert gs.predict(X[:8]).shape == (8,)
    assert gs.score(X, y) > 0.8


def test_grid_search_cv_many_classifier_matches_sequential():
    pytest.importorskip("sklearn")
    from sklearn.model_selection import KFold
    from lightgbm_tpu.multitrain import GridSearchCVMany
    from lightgbm_tpu.sklearn import LGBMClassifier
    X, y = _data(n=800)
    yb = (y > 0).astype(int)
    grid = {"reg_lambda": [0.0, 5.0]}
    est = LGBMClassifier(n_estimators=8, num_leaves=7, min_child_samples=5)
    gs = GridSearchCVMany(est, grid, cv=KFold(3), refit=False)
    gs.fit(X, yb)
    assert gs.cv_results_["mean_test_score"].shape == (2,)
    assert 0.5 < gs.best_score_ <= 1.0
