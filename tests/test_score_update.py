"""The training-set score update ``score + leaf_value[row_leaf]`` has two
lowerings: an XLA gather, and (on a TPU, up to ``SCORE_SELECT_MAX_LEAVES``
leaves) a streaming select over the rows, ``ops/histogram_pallas.py``
``score_update_pallas``.  They agree bit for bit, because a select hands
one f32 leaf value on untouched; here the kernel is interpreted on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.models import gbdt
from lightgbm_tpu.ops.histogram_pallas import score_update_pallas

select = jax.jit(gbdt._score_select_impl)

# values a select must hand on untouched: both zeros, denormals, the
# largest magnitudes (a sum, a product by a one-hot or a bf16 table would
# lose the zero's sign, flush or overflow)
SPECIAL = np.array([0.0, -0.0, 1e-45, -1e-45, 1.1754942e-38, 3e38, -3e38,
                    1.0, -1.0], np.float32)


def operands(n, leaves, tail, seed=0, one_leaf=False):
    rng = np.random.RandomState(seed)
    lv = rng.randn(leaves).astype(np.float32)
    at = rng.permutation(leaves)[:len(SPECIAL)]
    lv[at] = SPECIAL[:len(at)]
    rl = rng.randint(0, leaves, n + tail).astype(np.int32)
    rl[:min(leaves, n)] = np.arange(min(leaves, n))  # every leaf is hit
    if one_leaf:
        rl[:] = leaves - 1
    score = rng.randn(n).astype(np.float32)
    score[::7] = -0.0   # -0.0 + -0.0 keeps its sign, -0.0 + 0.0 does not
    return score, rl, lv


def bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("leaves", [2, 31, 255, 256])
@pytest.mark.parametrize("n", [64, 4097, 16384 + 3])
def test_select_has_the_gathers_bits(n, leaves):
    # row_leaf carries the grower's padded rows behind the score's
    for tail, one_leaf in ((0, False), (4096 - n % 4096, False), (5, True)):
        score, rl, lv = operands(n, leaves, tail, seed=n + leaves,
                                 one_leaf=one_leaf)
        # the reference is XLA's own gather and add (numpy keeps a
        # denormal sum that XLA flushes to zero), at the shrinkage every
        # caller passes: the leaf values arrive shrunk (at another one
        # XLA:CPU contracts the gather's product and sum into one FMA)
        want = gbdt._update_score_by_leaf(
            jnp.asarray(score), jnp.asarray(rl[:n]), jnp.asarray(lv), 1.0)
        got = select(jnp.asarray(score), jnp.asarray(rl), jnp.asarray(lv),
                     1.0)
        np.testing.assert_array_equal(bits(got), bits(want))
        normal = np.abs(lv[rl[:n]]) > 1e-30
        np.testing.assert_array_equal(bits(got)[normal],
                                      bits(score + lv[rl[:n]])[normal])


def test_select_refuses_fewer_leaf_ids_than_scores():
    with pytest.raises(ValueError, match="leaf ids"):
        score_update_pallas(jnp.zeros((64,)), jnp.zeros((63,), jnp.int32),
                            jnp.zeros((3,)))


@pytest.mark.parametrize("backend,leaves,want", [
    ("tpu", 2, "select"), ("tpu", 255, "select"),
    ("tpu", gbdt.SCORE_SELECT_MAX_LEAVES, "select"),
    ("tpu", gbdt.SCORE_SELECT_MAX_LEAVES + 1, "gather"),
    ("cpu", 255, "gather"), ("gpu", 31, "gather")])
def test_the_lowering_follows_backend_and_table_length(backend, leaves,
                                                       want):
    assert gbdt.score_update_lowering(backend, leaves) == want


@pytest.mark.parametrize("shards", [2, 8])
def test_select_over_row_shards_equals_the_unsharded_one(shards):
    from lightgbm_tpu.parallel.mesh import shard_rows
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:shards]), ("workers",))
    n = shards * 1500
    score, rl, lv = operands(n, 31, 0, seed=shards)
    want = select(jnp.asarray(score), jnp.asarray(rl), jnp.asarray(lv), 1.0)
    upd = gbdt._update_score_by_select_sharded(mesh, "workers")
    # a fresh, settled copy is donated (see test_analysis.py: XLA:CPU
    # frees a donated buffer under readers still in flight)
    placed = jax.block_until_ready(shard_rows(mesh, score.copy(), "workers"))
    got = upd(placed, shard_rows(mesh, rl, "workers"), jnp.asarray(lv), 1.0)
    assert got.sharding.spec == jax.sharding.PartitionSpec("workers")
    np.testing.assert_array_equal(bits(got), bits(want))
    text = upd.lower(shard_rows(mesh, score, "workers"),
                     shard_rows(mesh, rl, "workers"), jnp.asarray(lv),
                     1.0).compile().as_text()
    assert "all-gather" not in text and "all-reduce" not in text and \
        "collective-permute" not in text


def test_vmapped_multitrain_entry_equals_a_loop_over_models():
    """multitrain vmaps the undonated gather over the model axis; the
    select of each model alone gives the same scores."""
    models, n, leaves = 3, 4097, 31
    ops = [operands(n, leaves, 0, seed=m) for m in range(models)]
    score, rl, lv = (jnp.asarray(np.stack(v)) for v in zip(*ops))
    batched = jax.vmap(gbdt._update_score_by_leaf,
                       in_axes=(0, 0, 0, None))(score, rl, lv, 1.0)
    for m in range(models):
        one = select(score[m], rl[m], lv[m], 1.0)
        np.testing.assert_array_equal(bits(batched[m]), bits(one))


def test_a_booster_built_on_the_cpu_states_the_gather():
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, y), 2)
    assert bst.train_record.snapshot()["score_update"] == "gather"
    assert bst._gbdt._score_upd is gbdt._update_score_by_leaf


@pytest.fixture
def select_on_this_backend(monkeypatch):
    """The chooser as it decides on a TPU, with undonated twins of the
    select entries (XLA:CPU frees a donated buffer under readers still in
    flight, which is why the gather's donated entry is TPU-only too)."""
    from jax.sharding import PartitionSpec as P
    orig = gbdt.score_update_lowering
    monkeypatch.setattr(gbdt, "score_update_lowering",
                        lambda backend, leaves: orig("tpu", leaves))
    monkeypatch.setattr(gbdt, "_update_score_by_select_donated", select)
    built = []

    def sharded(mesh, axis):
        built.append(mesh)
        return jax.jit(jax.shard_map(
            gbdt._score_select_impl, mesh=mesh,
            in_specs=(P(axis), P(axis), P(), P()), out_specs=P(axis),
            check_vma=False))
    monkeypatch.setattr(gbdt, "_update_score_by_select_sharded", sharded)
    return built


@pytest.mark.parametrize("rows,params,want", [
    (4000, {}, "select"),
    (4000, {"objective": "multiclass", "num_class": 3}, "select"),
    (4000, {"tree_learner": "data", "num_devices": 4}, "sharded"),
    # rows that do not divide over the mesh stay on one device
    (4001, {"tree_learner": "data", "num_devices": 4}, "gather")])
def test_a_booster_on_the_select_grows_the_gathers_model(
        rows, params, want, select_on_this_backend, monkeypatch):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    X = rng.randn(rows, 6)
    y = (X[:, 0] + X[:, 1] ** 2 > 0.8).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "tpu_histogram_impl": "pallas", "tree_grow_mode": "wave",
              **params}

    bst_entry = []

    def train():
        bst = lgb.train(params, lgb.Dataset(X, y), 4)
        bst_entry[:] = [bst._gbdt._score_upd]
        return (bst.model_to_string(), bits(bst._gbdt.score),
                bst.train_record.snapshot()["score_update"])

    text, score, lowering = train()
    assert lowering == ("gather" if want == "gather" else "select")
    assert len(select_on_this_backend) == (want == "sharded")
    if want == "gather":
        # on this backend (off the TPU) the undonated entry
        assert bst_entry[0] is gbdt._update_score_by_leaf
    elif want == "select":
        assert bst_entry[0] is select
    monkeypatch.undo()
    text_g, score_g, lowering_g = train()
    assert lowering_g == "gather"
    assert text == text_g
    np.testing.assert_array_equal(score, score_g)
