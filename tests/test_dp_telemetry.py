"""What the data-parallel wave learner tells of itself: every collective sits
in an innermost ``lgbm.dp.*`` scope of the compiled program (the serial
program has none), and the run's ``TrainRecord`` holds the collectives it
traced and the mesh it built, with no tracing switch on."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.analysis import ir
from lightgbm_tpu.learner.wave import make_wave_grow_fn
from lightgbm_tpu.ops.histogram_pallas import pad_rows
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy
from lightgbm_tpu.parallel.mesh import get_mesh, shard_wave_grower

F, B, CHIPS, W = 6, 64, 4, 4
DP_SCOPES = {"lgbm.dp.hist_reduce", "lgbm.dp.exchange", "lgbm.dp.scalar"}
# q8 through the Pallas kernels (interpreted here), the ramp as it defaults
Q8 = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
      "min_data_in_leaf": 5, "tree_grow_mode": "wave",
      "tpu_histogram_impl": "pallas", "use_quantized_grad": True,
      "num_grad_quant_bins": 254, "quant_train_renew_leaf": True,
      "verbosity": -1}


def scopes_of(hlo_text: str) -> set:
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        named = [c for c in op_name.split("/") if c.startswith("lgbm.")]
        if named:
            found.add(named[-1])
    return found


def _grow_fn(strategy, scatter=False):
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    return make_wave_grow_fn(
        num_leaves=13, num_features=F, max_bins=B, max_depth=0,
        split_params=sp, hist_impl="pallas", any_cat=False, interpret=True,
        jit=False, wave_size=W, quantized=True, stochastic=False,
        spec_ramp=True, spec_tol=0.02, strategy=strategy)


def _args(n):
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randint(0, B - 1, (F, n)).astype(np.uint8)),
            jnp.asarray(rng.randn(n).astype(np.float32)),
            jnp.full((n,), 0.25, jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.full((F,), B, jnp.int32), jnp.zeros((F,), bool),
            jnp.zeros((F,), bool), jnp.zeros((F,), jnp.int32),
            jnp.zeros((F,), jnp.float32), jnp.ones((F,), bool))


def _dp_program(scatter):
    mesh = get_mesh(CHIPS)
    ax = mesh.axis_names[0]
    grow = _grow_fn(WaveDPStrategy(ax, nshards=CHIPS, hist_scatter=scatter))
    return shard_wave_grower(
        lambda X_T, g, h, m, nb, ic, hn, mono, cp, fm: grow(
            X_T, g, h, m, nb, ic, hn, mono, cp, (), fm), mesh, ax)


@pytest.mark.parametrize("scatter", [True, False],
                         ids=["reduce_scatter", "psum"])
def test_dp_wave_program_names_its_collectives(scatter):
    text = _dp_program(scatter).lower(
        *_args(CHIPS * pad_rows(3000))).compile().as_text()
    got = scopes_of(text)
    want = DP_SCOPES if scatter else DP_SCOPES - {"lgbm.dp.exchange"}
    assert want <= got, sorted(want - got)
    # innermost, so the phase they sit in is still in the name
    assert re.search(r'op_name="[^"]*lgbm\.[a-z_.]+/[^"]*lgbm\.dp\.hist_reduce',
                     text)


def test_serial_program_has_no_dp_scope():
    grow = _grow_fn(None)
    a = _args(pad_rows(3000))
    text = jax.jit(lambda *x: grow(*x[:9], (), x[9])).lower(*a) \
        .compile().as_text()
    got = scopes_of(text)
    assert "lgbm.ramp" in got and not {s for s in got
                                       if s.startswith("lgbm.dp.")}


def test_one_histogram_collective_a_pass():
    """Every full-data or provisional pass merges its batch ONCE: the
    traced program's reduce-scatters are exactly the tallied histogram
    sites (as tests/test_specramp.py counts the psum mode's)."""
    from lightgbm_tpu.telemetry.train_record import (collectives_reset,
                                                     collectives_snapshot)
    args = _args(CHIPS * pad_rows(3000))
    collectives_reset()
    prog = _dp_program(True)
    n_scatter = ir.count_primitive(ir.trace(lambda *a: prog(*a), *args),
                                   "reduce_scatter")
    site = collectives_snapshot()["data_parallel/wave/hist_reduce_scatter"]
    assert n_scatter == site["count"] > 0
    # the largest operand is the full wave batch: 2W channels, F padded to
    # the mesh, B bins, 3 int32 sums
    f_pad = -(-F // CHIPS) * CHIPS
    assert max(site["operand_sizes"]) % (f_pad * B * 3 * 4) == 0
    assert site["operand_bytes"] == CHIPS * site["bytes"]


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    X = rng.standard_normal((6000, F)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.standard_normal(6000) > 0)
    return X, y.astype(np.float32)


def _train(params, data, trees=3):
    X, y = data
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset([X[:2500], X[2500:]], y,
                                            params=params))
    for _ in range(trees):
        bst.update()
    return bst


def test_train_record_holds_collectives_and_mesh_without_a_switch(
        data, monkeypatch):
    monkeypatch.delenv("LGBM_TPU_TRACE", raising=False)
    p = dict(Q8, tree_learner="data", num_devices=CHIPS)
    # each record read before the next run traces: the tally is the
    # process's, and a record counts what was traced since it began
    snaps = []
    for _ in range(2):
        bst = _train(p, data)
        snaps.append(bst.train_record.snapshot())
    bins = bst._gbdt.max_bins
    for snap in snaps:    # the second booster's record is filled as the first
        assert snap["mesh"] == {"chips": CHIPS, "axis": "workers",
                                "rows_per_chip": 1500}
        sites = snap["collectives"]
        assert {s for s in sites if s.startswith("data_parallel/wave/")} >= {
            "data_parallel/wave/hist_reduce_scatter",
            "data_parallel/wave/winner_exchange",
            "data_parallel/wave/scalar_sum", "data_parallel/wave/quant_scale"}
        hist = sites["data_parallel/wave/hist_reduce_scatter"]
        # one pass's operand: the local (channels, F padded to the mesh,
        # bins, 3) int32 batch
        f_pad = -(-F // CHIPS) * CHIPS
        assert hist["max_operand_bytes"] % (f_pad * bins * 3 * 4) == 0
        assert hist["operand_bytes"] == CHIPS * hist["bytes"]
        assert "shard" in snap["setup_seconds"]
    assert snaps[0]["collectives"] == snaps[1]["collectives"]
    # the placement: bins, scores and the feature-major matrix are row shards
    g = bst._gbdt
    for arr in (g.X_dev, g.score, g.learner._XpT):
        assert len({s.device for s in arr.addressable_shards}) == CHIPS
        assert all(s.data.size * CHIPS == arr.size
                   for s in arr.addressable_shards)


def test_a_larger_program_traced_earlier_does_not_leak_into_a_record(data):
    """``max_operand_bytes`` is the largest operand traced since the record
    was made, not the process's: a wider booster first, then a narrower."""
    p = dict(Q8, tree_learner="data", num_devices=CHIPS)
    site = "data_parallel/wave/hist_reduce_scatter"
    wide = _train(dict(p, max_bin=127), data, trees=1)
    narrow = _train(dict(p, max_bin=31), data, trees=1)
    big = wide.train_record.snapshot()["collectives"][site]
    small = narrow.train_record.snapshot()["collectives"][site]
    assert small["max_operand_bytes"] < big["max_operand_bytes"]
    f_pad = -(-F // CHIPS) * CHIPS
    assert small["max_operand_bytes"] % (
        f_pad * narrow._gbdt.max_bins * 3 * 4) == 0


def test_serial_record_says_one_chip_and_no_collectives(data):
    snap = _train(Q8, data, trees=1).train_record.snapshot()
    assert snap["mesh"] == {"chips": 1, "axis": None, "rows_per_chip": 6000}
    assert snap["collectives"] == {}
    assert "shard" not in snap["setup_seconds"]
