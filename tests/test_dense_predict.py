"""Inference-compiler tests (serve/compiler.py + models/dense_predict.py):
bitwise/tolerance parity of the fused dense program against the
sequential walk across categorical (incl. multi-word bitsets),
NaN/missing, multiclass, linear leaves, pred-leaf routing and bucket
boundary shapes; jaxpr structure assertions (zero while loops, exactly
one psum sharded); fallback telemetry; quantized-leaf tolerance; the
serve_dense lint config."""

import numpy as np
import pytest

import lightgbm_tpu as lgb

SMALL = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1}


def _cat_model(num_leaves=7, trees=10, max_cat=70):
    """Binary model splitting on a categorical with values up to
    ``max_cat`` — past 32 the bitsets span MULTIPLE uint32 words."""
    rng = np.random.RandomState(5)
    n = 600
    X = rng.randn(n, 6)
    X[:, 3] = rng.randint(0, max_cat, n)
    y = ((X[:, 3] % 3 == 0) * 2.0 + 0.3 * X[:, 0] +
         0.3 * rng.randn(n) > 1.0).astype(np.float64)
    p = {**SMALL, "objective": "binary", "num_leaves": num_leaves}
    ds = lgb.Dataset(X, y, categorical_feature=[3], params=p)
    return lgb.train(p, ds, trees)


def _cat_queries(n, max_cat=80, nan_rows=True):
    rng = np.random.RandomState(11)
    Xq = rng.randn(n, 6)
    Xq[:, 3] = rng.randint(0, max_cat, n)  # incl. unseen categories
    if nan_rows and n >= 4:
        Xq[1, 3] = np.nan       # NaN categorical -> default direction
        Xq[2, 0] = np.nan       # NaN numeric
        Xq[3, 3] = 3.5          # non-integer category -> not a member
    return Xq


@pytest.fixture(scope="module")
def cat_booster():
    return _cat_model()


# -- parity matrix ----------------------------------------------------------
def test_dense_vs_walk_parity_categorical(cat_booster):
    """Multi-word bitset membership as a contraction == the sequential
    FindInBitset walk, to f32-sum tolerance; dense predictor == dense
    Booster.predict bitwise (same compiled program)."""
    bst = cat_booster
    Xq = _cat_queries(37)
    dense = bst.to_predictor(compiler="dense")
    walk = bst.to_predictor(compiler="walk")
    assert dense.info()["compiler"] == "dense"
    assert dense.info()["dense"]["has_cat"]
    out_d = dense.predict(Xq, raw_score=True)
    out_w = walk.predict(Xq, raw_score=True)
    np.testing.assert_allclose(out_d, out_w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("compiler", ["dense", "walk"])
def test_threshold_between_float32_neighbours(compiler):
    """A float64 threshold that rounds UP to float32 (found on the chip,
    PR 27: benchmark seed 2147495087, held-out row 18094): the row whose
    value is that float32 lies ABOVE the threshold and goes right, as the
    model text's float64 compare sends it; its lower neighbour goes left."""
    import re
    thr = -0.3956146985292434
    above = np.float32(thr)
    assert float(above) > thr
    below = np.nextafter(above, np.float32(-np.inf))
    rng = np.random.RandomState(3)
    X = rng.randn(200, 2)
    y = (X[:, 0] > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 2, "min_data_in_leaf": 5,
         "verbosity": -1}
    text = lgb.train(p, lgb.Dataset(X, y, params=p), 1).model_to_string()
    text, n = re.subn(r"(?m)^threshold=.*$", f"threshold={thr!r}", text)
    assert n == 1
    bst = lgb.Booster(model_str=text)
    root = bst.dump_model()["tree_info"][0]["tree_structure"]
    left = root["left_child"]["leaf_value"]
    right = root["right_child"]["leaf_value"]
    assert left != right
    Xq = np.array([[above, 0.0], [below, 0.0]], np.float64)
    got = bst.to_predictor(compiler=compiler).predict(Xq, raw_score=True)
    np.testing.assert_allclose(got, [right, left], rtol=1e-6)


def test_dense_multiclass_parity(multiclass_data):
    X, y = multiclass_data
    p = {**SMALL, "objective": "multiclass", "num_class": 3}
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), 6)
    dense = bst.to_predictor(compiler="dense")
    walk = bst.to_predictor(compiler="walk")
    rng = np.random.RandomState(3)
    Xq = rng.randn(23, 6)
    Xq[4, 1] = np.nan
    out_d = dense.predict(Xq)
    assert out_d.shape == (23, 3)
    np.testing.assert_allclose(out_d, walk.predict(Xq), rtol=1e-5,
                               atol=1e-6)


def test_dense_linear_leaves_parity(regression_data):
    """Linear leaves = leaf-gather + matmul in the fused program, with
    the reference NaN fallback to the plain leaf output."""
    X, y = regression_data
    p = {**SMALL, "objective": "regression", "linear_tree": True}
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), 8)
    dense = bst.to_predictor(compiler="dense")
    walk = bst.to_predictor(compiler="walk")
    assert dense.info()["dense"]["has_linear"]
    rng = np.random.RandomState(6)
    Xq = rng.randn(15, 6)
    Xq[3, 0] = np.nan
    Xq[7, :] = np.nan
    np.testing.assert_allclose(dense.predict(Xq), walk.predict(Xq),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [1, 7, 8, 9, 63, 65, 511, 513])
def test_dense_bucket_boundary_parity(n, cat_booster):
    """N = bucket +- 1 shapes: the dense predictor is bitwise identical
    to Booster.predict when both route dense (one shared program per
    bucket), and walk-close everywhere."""
    bst = cat_booster
    Xq = _cat_queries(n, nan_rows=n >= 4)
    dense = bst.to_predictor(compiler="dense")
    ref = bst._gbdt  # route Booster.predict through the same compiler
    old = ref.config.tpu_predict_compiler
    try:
        ref.config.tpu_predict_compiler = "dense"
        assert np.array_equal(dense.predict(Xq), bst.predict(Xq))
    finally:
        ref.config.tpu_predict_compiler = old


def test_dense_pred_leaf_routing(cat_booster):
    """pred_leaf through the compiled program (argmax of the hit
    one-hot) == the per-tree walk's leaf indices, exactly."""
    bst = cat_booster
    Xq = _cat_queries(9)
    cfg = bst._gbdt.config
    old = cfg.tpu_predict_compiler
    try:
        cfg.tpu_predict_compiler = "dense"
        leaves_d = bst.predict(Xq, pred_leaf=True)
        cfg.tpu_predict_compiler = "walk"
        leaves_w = bst.predict(Xq, pred_leaf=True)
    finally:
        cfg.tpu_predict_compiler = old
    assert np.array_equal(leaves_d, leaves_w)


def test_dense_stump_and_mixed_depth():
    """num_leaves-2 stumpy trees and unbalanced trees resolve through
    the same satisfied-count program."""
    rng = np.random.RandomState(9)
    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 2, "min_data_in_leaf": 5,
         "verbosity": -1}
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), 5)
    dense = bst.to_predictor(compiler="dense")
    walk = bst.to_predictor(compiler="walk")
    Xq = rng.randn(9, 4)
    np.testing.assert_allclose(dense.predict(Xq), walk.predict(Xq),
                               rtol=1e-6, atol=1e-7)


# -- quantized leaf tables --------------------------------------------------
@pytest.mark.parametrize("bits", [8, 16])
def test_quantized_leaf_tolerance(bits, cat_booster):
    """i8/i16 leaf codes dequantized in the final contraction: absolute
    error bounded by sum of per-tree scales / 2 (bit-controlled)."""
    bst = cat_booster
    Xq = _cat_queries(64)
    exact = bst.to_predictor(compiler="dense", leaf_bits=0)
    quant = bst.to_predictor(compiler="dense", leaf_bits=bits)
    assert quant.info()["dense"]["leaf_bits"] == bits
    out_e = exact.predict(Xq, raw_score=True)
    out_q = quant.predict(Xq, raw_score=True)
    scales = np.asarray(quant._dense.arrays.leaf_scale).ravel()
    tol = scales.sum() / 2 + 1e-6
    assert np.max(np.abs(out_q - out_e)) <= tol
    if bits == 16:
        # 16-bit codes are 256x finer than 8-bit
        q8 = bst.to_predictor(compiler="dense", leaf_bits=8)
        err16 = np.max(np.abs(out_q - out_e))
        err8 = np.max(np.abs(q8.predict(Xq, raw_score=True) - out_e))
        assert err16 <= err8 + 1e-12


# -- jaxpr structure --------------------------------------------------------
def test_dense_program_has_no_loops(cat_booster):
    """The compiled dense program is loop-free: zero while/scan in the
    jaxpr at every bucket (the whole point — no sequential tree walk,
    no depth loop)."""
    import jax
    from lightgbm_tpu.analysis import ir
    from lightgbm_tpu.models.dense_predict import dense_predict_raw
    from lightgbm_tpu.models.tree import pad_rows
    pred = cat_booster.to_predictor(compiler="dense")
    exe = pred._dense
    for n in (1, 64, 513):
        Xp = pad_rows(np.zeros((n, 6), np.float32))
        jx = jax.make_jaxpr(
            lambda X, A: dense_predict_raw(X, A, exe.meta))(Xp, exe.arrays)
        assert ir.count_primitive(jx, "while") == 0
        assert ir.count_primitive(jx, "scan") == 0
        assert ir.count_primitive(jx, "psum") == 0


def test_dense_sharded_one_psum(cat_booster):
    """Tree-axis sharding: per-shard partials merge in EXACTLY one psum
    and the result matches the unsharded program to f32 tolerance."""
    import jax
    from lightgbm_tpu.analysis import ir
    from lightgbm_tpu.models.tree import pad_rows
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    pred = cat_booster.to_predictor(compiler="dense")
    sharded = cat_booster.to_predictor(compiler="dense", shard=4)
    assert sharded.info()["dense"]["shard"] == 4
    exe = sharded._dense
    Xp = pad_rows(np.zeros((9, 6), np.float32))
    jx = jax.make_jaxpr(lambda X, A: exe._sharded_fn(X, A))(Xp, exe.arrays)
    assert ir.count_primitive(jx, "psum") == 1
    assert ir.count_primitive(jx, "while") == 0
    Xq = _cat_queries(37)
    np.testing.assert_allclose(sharded.predict(Xq, raw_score=True),
                               pred.predict(Xq, raw_score=True),
                               rtol=1e-5, atol=1e-6)


# -- fallback telemetry -----------------------------------------------------
def test_fallback_reason_recorded(cat_booster):
    """Auto-mode walks are never silent: the reason lands in info() and
    the serve_compiler_fallback counter."""
    from lightgbm_tpu.serve.compiler import fallback_counts
    from lightgbm_tpu.serve import compile_ensemble
    g = cat_booster._gbdt
    before = fallback_counts()
    # a categorical with a huge raw value blows the bitset-table budget
    import lightgbm_tpu.models.dense_predict as dp
    exe, reason = compile_ensemble(
        g.models, 1, 6, mode="auto")
    if exe is None:
        assert reason  # whatever auto decided, it said why
    # force a budget fallback deterministically
    import lightgbm_tpu.serve.compiler as comp

    def tiny_budget_lower(*a, **kw):
        kw["cat_budget"] = 1
        return dp.lower_ensemble(*a, **kw)

    orig = comp.lower_ensemble
    comp.lower_ensemble = tiny_budget_lower
    try:
        exe2, reason2 = comp.compile_ensemble(g.models, 1, 6, mode="auto")
    finally:
        comp.lower_ensemble = orig
    assert exe2 is None and reason2 == "cat_table_budget"
    after = fallback_counts()
    assert after.get("cat_table_budget", 0) > before.get(
        "cat_table_budget", 0)
    # dense mode raises instead of silently walking
    comp.lower_ensemble = tiny_budget_lower
    try:
        with pytest.raises(comp.DenseLoweringError):
            comp.compile_ensemble(g.models, 1, 6, mode="dense")
    finally:
        comp.lower_ensemble = orig


def test_forced_walk_reason(cat_booster):
    pred = cat_booster.to_predictor(compiler="walk")
    assert pred.info()["compiler"] == "walk"
    assert pred.info()["fallback_reason"] == "forced_walk"


def test_cost_model_backend_awareness():
    from lightgbm_tpu.serve.compiler import dense_cost_model
    # the MXU always profits (per-row gathers are the slow primitive)
    assert dense_cost_model(50, 255, 30, backend="tpu")
    # on CPU, deep wide trees keep the walk; shallow ensembles go dense
    assert not dense_cost_model(50, 255, 30, backend="cpu")
    assert dense_cost_model(50, 4, 3, backend="cpu")


def test_compiler_param_validation():
    from lightgbm_tpu.config import Config
    with pytest.raises(ValueError):
        Config({"tpu_predict_compiler": "bogus"})
    with pytest.raises(ValueError):
        Config({"tpu_predict_leaf_bits": 5})


def test_auto_consistency_booster_vs_predictor(cat_booster):
    """Whatever auto decides, Booster.predict and the predictor decide
    it IDENTICALLY (same cost model, same trees) and match bitwise."""
    bst = cat_booster
    Xq = _cat_queries(9)
    pred = bst.to_predictor()  # auto from the model's params
    assert np.array_equal(pred.predict(Xq), bst.predict(Xq))


# -- serve_dense lint config ------------------------------------------------
def test_serve_dense_lint_config_clean():
    """The serve_dense trace-lint config (bucket-ladder retrace probes +
    the sharded psum contract) runs clean at head."""
    from lightgbm_tpu.analysis.lint import ALL_RULES, build_unit
    from lightgbm_tpu.analysis.rules import run_rules
    unit = build_unit("serve_dense", nshards=4)
    assert unit.jaxpr is not None
    violations = run_rules([unit], rules=ALL_RULES)
    assert not violations, [v.to_json() for v in violations]
    # the ladder stays within its distinct-program bound and the main
    # trace carries the one-psum tally
    assert unit.ctx["max_distinct_programs"] >= len(
        {h for _, h in unit.hashes})
    assert "serve/dense_predict/score_psum" in unit.collectives
