"""Chunked streamed training (tpu_ingest_mode=chunked): bit-identity to
in-core training on the quantized matrix, f32 parity, chunk-boundary
shapes, resume-mid-stream via the PR-6 checkpoint path, envelope
errors and GOSS thinning."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ingest import (ArraySource, StreamedDataset,
                                 StreamedEnvelopeError, train_streamed)


def _data(n=3001, f=6, seed=7, task="binary"):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    raw = X[:, 0] + 0.5 * X[:, 1] + rng.randn(n) * 0.5
    if task == "binary":
        y = (raw > 0).astype(np.float64)
    elif task == "mc":
        y = np.digitize(raw, [-0.5, 0.5]).astype(np.float64)
    else:
        y = raw
    return X, y


# the chunked grower's envelope, pinned identically for both runs: wave
# grower, taper tail (endgame/spec off), deterministic rounding
_PIN = {"verbosity": -1, "num_leaves": 15, "learning_rate": 0.2,
        "max_bin": 63, "min_data_in_leaf": 5, "enable_bundle": False,
        "seed": 3, "tree_grow_mode": "wave", "tpu_exact_endgame": False,
        "tpu_speculative_ramp": False, "stochastic_rounding": False}


def _both(params, X, y, rounds=6, chunk_rows=512):
    ds = lgb.Dataset(X.copy(), label=y.copy())
    b1 = lgb.train(params, ds, num_boost_round=rounds)
    sd = StreamedDataset(ArraySource(X, y, chunk_rows=chunk_rows),
                         params=params)
    b2 = train_streamed(params, sd, num_boost_round=rounds)
    return b1, b2


# ---------------------------------------------------------------------------
# bit-identity: quantized matrix (int32 histogram sums are exact under
# any chunk partition, so streamed == in-core bit for bit)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,extra", [
    # W=1 reproduces the TRUE sequential best-first order (wave.py docs)
    ("serial_order", {"use_quantized_grad": True, "tpu_wave_size": 1}),
    ("wave", {"use_quantized_grad": True, "tpu_wave_size": 4}),
    ("quantized_default_wave", {"use_quantized_grad": True}),
    ("quantized_16bins", {"use_quantized_grad": True,
                          "num_grad_quant_bins": 16, "tpu_wave_size": 4}),
])
def test_chunked_bit_identity(name, extra):
    X, y = _data()
    p = dict(_PIN, objective="binary")
    p.update(extra)
    b1, b2 = _both(p, X, y)
    assert b1.model_to_string() == b2.model_to_string(), name
    assert np.array_equal(b1.predict(X[:64]), b2.predict(X[:64]))


def test_chunked_bit_identity_regression():
    X, y = _data(task="regression")
    p = dict(_PIN, objective="regression", use_quantized_grad=True,
             tpu_wave_size=4)
    b1, b2 = _both(p, X, y)
    assert b1.model_to_string() == b2.model_to_string()


def test_chunked_matches_dp_scatter_structure():
    """The DP rung's BIT-identity is covered on the hbm route
    (test_ingest.py::test_hbm_route_bit_identity[dp_scatter] — same
    program, streamed ingestion).  Here the CHUNKED trainer is compared
    against an in-core DP-wave reduce-scatter run: identical tree
    structures and f32-tolerance outputs (the in-core DP path's winner
    exchange re-derives recorded gain/weight fields from dequantized
    payloads, which drifts the last f32 ulps vs the serial grower on
    this config — so bitwise equality is not the right bar between the
    two in-core paths either)."""
    import jax
    if jax.device_count() < 8:
        pytest.skip("needs 8 (virtual) devices")
    X, y = _data(4096, 6)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             tpu_wave_size=4)
    dp = dict(p, tree_learner="data", num_machines=8, num_devices=8,
              tpu_dp_hist_scatter=True)
    ds = lgb.Dataset(X.copy(), label=y.copy())
    b_dp = lgb.train(dp, ds, num_boost_round=4)
    sd = StreamedDataset(ArraySource(X, y, chunk_rows=512), params=p)
    b_st = train_streamed(p, sd, num_boost_round=4)
    s1 = [(t.split_feature.tolist(), t.threshold_bin.tolist())
          for t in b_dp._gbdt.models]
    s2 = [(t.split_feature.tolist(), t.threshold_bin.tolist())
          for t in b_st._gbdt.models]
    assert s1 == s2
    assert np.allclose(b_dp.predict(X), b_st.predict(X), atol=1e-5)


# ---------------------------------------------------------------------------
# f32 path: same structure, f32-tolerance outputs
# ---------------------------------------------------------------------------

def test_chunked_f32_structure_and_tolerance():
    X, y = _data()
    p = dict(_PIN, objective="binary", tpu_wave_size=4)
    b1, b2 = _both(p, X, y)
    s1 = [t.split_feature.tolist() for t in b1._gbdt.models]
    s2 = [t.split_feature.tolist() for t in b2._gbdt.models]
    assert s1 == s2
    assert np.allclose(b1.predict(X), b2.predict(X), atol=1e-5)


def test_chunked_bit_identity_pallas_interpret():
    """The Pallas chunk path (the on-TPU configuration: fused row-update
    kernel + q8 leaf-channel kernel per chunk) in interpret mode, vs the
    in-core pallas-interpret run — int32 accumulation stays exact across
    the kernel boundary too."""
    X, y = _data(8192, 4, seed=5)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             num_leaves=7, max_bin=15, tpu_wave_size=2,
             tpu_histogram_impl="pallas", tpu_hist_pack4=False)
    b1, b2 = _both(p, X, y, rounds=2, chunk_rows=4096)
    assert b1.model_to_string() == b2.model_to_string()


# ---------------------------------------------------------------------------
# chunk-boundary shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2048, 2049])
def test_chunked_boundary_shapes(n):
    X, y = _data(n, 5, seed=11)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             tpu_wave_size=4)
    b1, b2 = _both(p, X, y, rounds=4)
    assert b1.model_to_string() == b2.model_to_string()


# ---------------------------------------------------------------------------
# engine.train routing + envelope errors
# ---------------------------------------------------------------------------

def test_engine_routes_chunked_mode():
    X, y = _data(2048, 5, seed=2)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             tpu_wave_size=4)
    sd = StreamedDataset(ArraySource(X, y, chunk_rows=512),
                         params=dict(p, tpu_ingest_mode="chunked"))
    bst = lgb.train(dict(p, tpu_ingest_mode="chunked"), sd,
                    num_boost_round=3)
    ds = lgb.Dataset(X.copy(), label=y.copy())
    t1 = lgb.train(p, ds, num_boost_round=3).model_to_string()
    # tpu_ingest_mode is excluded from the params dump, so the streamed
    # route's model text matches the in-core twin byte for byte
    assert bst.model_to_string() == t1


def test_engine_chunked_rejects_callbacks():
    X, y = _data(2048, 5, seed=2)
    p = dict(_PIN, objective="binary", tpu_ingest_mode="chunked")
    sd = StreamedDataset(ArraySource(X, y, chunk_rows=512), params=p)
    with pytest.raises(ValueError, match="callbacks"):
        lgb.train(p, sd, num_boost_round=2, callbacks=[lambda env: None])


def test_envelope_errors():
    X, y = _data(2048, 5, seed=2)
    sd = StreamedDataset(ArraySource(X, y, chunk_rows=512),
                         params={"verbosity": -1})
    with pytest.raises(StreamedEnvelopeError, match="objective"):
        train_streamed(dict(_PIN, objective="poisson"), sd, 2)
    with pytest.raises(StreamedEnvelopeError, match="monotone"):
        train_streamed(dict(_PIN, objective="binary",
                            monotone_constraints=[1, 0, 0, 0, 0]), sd, 2)
    # ranking stays in-core only (query segments are not chunk-sliceable);
    # multiclassova's per-class label weights likewise
    with pytest.raises(StreamedEnvelopeError, match="objective"):
        train_streamed({"objective": "lambdarank", "verbosity": -1}, sd, 2)
    with pytest.raises(StreamedEnvelopeError, match="objective"):
        train_streamed({"objective": "multiclassova", "num_class": 3,
                        "verbosity": -1}, sd, 2)
    # dart batches now, but not with checkpointing (drop weights are not
    # reconstructible from model text)
    with pytest.raises(StreamedEnvelopeError, match="checkpoint"):
        train_streamed(dict(_PIN, objective="binary", boosting="dart",
                            snapshot_freq=1), sd, 2)


# ---------------------------------------------------------------------------
# bagging / feature_fraction parity, GOSS thinning
# ---------------------------------------------------------------------------

def test_chunked_bagging_feature_fraction_identity():
    X, y = _data()
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             tpu_wave_size=4, bagging_fraction=0.7, bagging_freq=2,
             feature_fraction=0.8)
    b1, b2 = _both(p, X, y)
    assert b1.model_to_string() == b2.model_to_string()


def test_chunked_goss_bit_identity():
    """GOSS rides the ONE sampler (models.gbdt.goss_sample, here through
    its host face goss_sample_np): the streamed run thins exactly the rows the in-core run thins,
    warmup included, so the quantized model text matches byte for
    byte."""
    X, y = _data(4096, 6)
    p = dict(_PIN, objective="binary", boosting="goss",
             use_quantized_grad=True, tpu_wave_size=4,
             learning_rate=0.5, top_rate=0.2, other_rate=0.1)
    b1, b2 = _both(p, X, y)
    assert b1.model_to_string() == b2.model_to_string()
    pred = b2.predict(X)
    acc = float(((pred > 0.5) == (y > 0)).mean())
    assert acc > 0.7


@pytest.mark.parametrize("extra", [
    {"uniform_drop": True},
    {"uniform_drop": False, "xgboost_dart_mode": True, "max_drop": 3},
])
def test_chunked_dart_bit_identity(extra):
    """DART's drop/Normalize bookkeeping replayed host-side (same
    (drop_seed, iteration) streams, f32 axpys) == the in-core device
    run, in both drop modes."""
    X, y = _data()
    p = dict(_PIN, objective="binary", boosting="dart",
             use_quantized_grad=True, tpu_wave_size=4, drop_rate=0.5,
             drop_seed=9)
    p.update(extra)
    b1, b2 = _both(p, X, y, rounds=8)
    assert b1.model_to_string() == b2.model_to_string()
    assert np.array_equal(b1.predict(X[:64]), b2.predict(X[:64]))


def test_chunked_multiclass_bit_identity():
    """Softmax gradients are rowwise -> chunk-sliceable; the K-tree
    iteration grid matches the in-core class loop byte for byte."""
    X, y = _data(task="mc")
    p = dict(_PIN, objective="multiclass", num_class=3,
             use_quantized_grad=True, tpu_wave_size=4)
    b1, b2 = _both(p, X, y)
    assert b1.model_to_string() == b2.model_to_string()
    assert np.array_equal(b1.predict(X[:64]), b2.predict(X[:64]))


@pytest.mark.slow
def test_chunked_multiclass_bagging_feature_fraction_identity():
    X, y = _data(task="mc")
    p = dict(_PIN, objective="multiclass", num_class=3,
             use_quantized_grad=True, tpu_wave_size=4,
             bagging_fraction=0.7, bagging_freq=2, feature_fraction=0.8)
    b1, b2 = _both(p, X, y)
    assert b1.model_to_string() == b2.model_to_string()


# ---------------------------------------------------------------------------
# streamed validation + early stopping: same stop round as in-core
# ---------------------------------------------------------------------------

def _split(X, y, cut=3000):
    return X[:cut], y[:cut], X[cut:], y[cut:]


@pytest.mark.slow
def test_chunked_early_stop_same_round():
    X, y = _data(4096, 6)
    Xtr, ytr, Xv, yv = _split(X, y)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             tpu_wave_size=4, early_stopping_round=3)
    ds = lgb.Dataset(Xtr.copy(), label=ytr.copy())
    dv = lgb.Dataset(Xv.copy(), label=yv.copy(), reference=ds)
    b1 = lgb.train(p, ds, num_boost_round=60, valid_sets=[dv],
                   valid_names=["va"])
    pc = dict(p, tpu_ingest_mode="chunked")
    sd = StreamedDataset(ArraySource(Xtr, ytr, chunk_rows=512), params=pc)
    sv = StreamedDataset(ArraySource(Xv, yv, chunk_rows=512), params=pc)
    b2 = lgb.train(pc, sd, num_boost_round=60, valid_sets=[sv],
                   valid_names=["va"])
    assert b1.best_iteration == b2.best_iteration
    # the streamed valid walk sees the same f32 scores -> same metric
    assert b1.best_score == b2.best_score
    assert b1.model_to_string() == b2.model_to_string()


@pytest.mark.slow
def test_chunked_early_stop_in_core_valid():
    """An in-core Dataset as the valid of a chunked streamed run (mixed
    types): binned against the streamed train's mappers via reference."""
    X, y = _data(4096, 6)
    Xtr, ytr, Xv, yv = _split(X, y)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             tpu_wave_size=4, early_stopping_round=3,
             tpu_ingest_mode="chunked")
    sd = StreamedDataset(ArraySource(Xtr, ytr, chunk_rows=512), params=p)
    dv = lgb.Dataset(Xv.copy(), label=yv.copy())
    b = lgb.train(p, sd, num_boost_round=60, valid_sets=[dv])
    assert b.best_iteration > 0
    assert "valid_0" in b.best_score


@pytest.mark.slow
def test_chunked_dart_early_stop_same_round():
    X, y = _data(4096, 6)
    Xtr, ytr, Xv, yv = _split(X, y)
    p = dict(_PIN, objective="binary", boosting="dart", drop_rate=0.5,
             drop_seed=9, use_quantized_grad=True, tpu_wave_size=4,
             early_stopping_round=4)
    ds = lgb.Dataset(Xtr.copy(), label=ytr.copy())
    dv = lgb.Dataset(Xv.copy(), label=yv.copy(), reference=ds)
    b1 = lgb.train(p, ds, num_boost_round=25, valid_sets=[dv])
    pc = dict(p, tpu_ingest_mode="chunked")
    sd = StreamedDataset(ArraySource(Xtr, ytr, chunk_rows=512), params=pc)
    sv = StreamedDataset(ArraySource(Xv, yv, chunk_rows=512), params=pc)
    b2 = lgb.train(pc, sd, num_boost_round=25, valid_sets=[sv])
    assert b1.best_iteration == b2.best_iteration
    assert b1.model_to_string() == b2.model_to_string()


@pytest.mark.slow
def test_chunked_multiclass_goss_early_stop_same_round():
    X, y = _data(4096, 6, task="mc")
    Xtr, ytr, Xv, yv = _split(X, y)
    p = dict(_PIN, objective="multiclass", num_class=3, boosting="goss",
             learning_rate=0.5, top_rate=0.2, other_rate=0.1,
             use_quantized_grad=True, tpu_wave_size=4,
             early_stopping_round=3)
    ds = lgb.Dataset(Xtr.copy(), label=ytr.copy())
    dv = lgb.Dataset(Xv.copy(), label=yv.copy(), reference=ds)
    b1 = lgb.train(p, ds, num_boost_round=40, valid_sets=[dv])
    pc = dict(p, tpu_ingest_mode="chunked")
    sd = StreamedDataset(ArraySource(Xtr, ytr, chunk_rows=512), params=pc)
    sv = StreamedDataset(ArraySource(Xv, yv, chunk_rows=512), params=pc)
    b2 = lgb.train(pc, sd, num_boost_round=40, valid_sets=[sv])
    assert b1.best_iteration == b2.best_iteration
    assert b1.model_to_string() == b2.model_to_string()


# ---------------------------------------------------------------------------
# resume-mid-stream via the PR-6 checkpoint path
# ---------------------------------------------------------------------------

def test_resume_mid_stream_bit_identical(tmp_path):
    X, y = _data()
    # checkpoint cadence params stay IDENTICAL between the uninterrupted
    # and the resumed run (only resume/checkpoint_dir are excluded from
    # the model-text params dump)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             tpu_wave_size=4, snapshot_freq=2,
             checkpoint_dir=str(tmp_path / "ck_full"))
    # uninterrupted run
    sd = StreamedDataset(ArraySource(X, y, chunk_rows=512), params=p)
    full = train_streamed(p, sd, num_boost_round=8).model_to_string()
    # interrupted at iteration 4, resumed from the bundle: the bundle's
    # fingerprint is the streamed crc and must match the re-streamed
    # dataset across the "restart"
    ck = dict(p, checkpoint_dir=str(tmp_path / "ck"))
    sd1 = StreamedDataset(ArraySource(X, y, chunk_rows=512), params=ck)
    train_streamed(ck, sd1, num_boost_round=4)
    sd2 = StreamedDataset(ArraySource(X, y, chunk_rows=512),
                          params=dict(ck, resume="latest"))
    resumed = train_streamed(dict(ck, resume="latest"), sd2,
                             num_boost_round=8)
    assert resumed.model_to_string() == full
    assert sd1.fingerprint() == sd2.fingerprint()


def test_resume_rejects_fingerprint_mismatch(tmp_path):
    from lightgbm_tpu.resilience.checkpoint import CheckpointError
    X, y = _data(2048, 5, seed=2)
    p = dict(_PIN, objective="binary", use_quantized_grad=True,
             checkpoint_dir=str(tmp_path / "ck"), snapshot_freq=1)
    sd = StreamedDataset(ArraySource(X, y, chunk_rows=512), params=p)
    train_streamed(p, sd, num_boost_round=2)
    X2 = X.copy()
    X2[0, 0] += 1.0  # different data -> different streamed crc
    sd2 = StreamedDataset(ArraySource(X2, y, chunk_rows=512),
                          params=dict(p, resume="latest"))
    with pytest.raises(CheckpointError, match="fingerprint|match"):
        train_streamed(dict(p, resume="latest"), sd2, num_boost_round=4)
