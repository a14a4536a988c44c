"""The GOSS draw is ONE jitted function (``models/gbdt.py`` ``goss_sample``):
held here to a plain numpy statement of its law, to its host face
(``goss_sample_np``, what the chunked and the multi-model trainer call), to
itself on row shards, and to the names it carries in the trace and the
record."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.models.gbdt import (GOSS_OUT, GOSS_REST, GOSS_TOP, goss_rates, goss_sample,
                                      goss_sample_np)

A, B, SEED = 0.2, 0.1, 3
N = 40_003


def grads(n=N, classes=0, seed=0):
    rng = np.random.default_rng(seed)
    shape = (n,) if not classes else (n, classes)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.random(shape).astype(np.float32) + np.float32(0.05))


def cfg(**kw):
    return SimpleNamespace(**dict(dict(top_rate=A, other_rate=B, learning_rate=0.1,
                                       bagging_seed=SEED), **kw))


def draw(g, h, it=12, a=A, b=B, seed=SEED):
    return goss_sample(g, h, it, top_rate=a, other_rate=b, bagging_seed=seed)


def numpy_top(score, a):
    n = len(score)
    k = max(1, int(n * a))
    return score >= np.partition(score, n - k)[n - k]


@pytest.mark.parametrize("a, b", [(0.2, 0.1), (0.05, 0.5), (0.5, 0.25)])
def test_the_top_set_is_exact_and_the_rest_is_drawn_at_its_rate(a, b):
    g, h = grads()
    cls, mask, gs, hs, count = (np.asarray(x) for x in draw(g, h, a=a, b=b))
    assert cls.dtype == np.uint8 and set(np.unique(cls)) == {GOSS_OUT, GOSS_TOP, GOSS_REST}
    top = numpy_top(np.abs(g * h), a)
    assert np.array_equal(cls == GOSS_TOP, top)
    assert top.sum() == max(1, int(N * a))           # no ties in continuous scores
    n_rest, p = int((~top).sum()), b / (1 - a)
    kept = int((cls == GOSS_REST).sum())
    assert abs(kept - n_rest * p) < 6 * np.sqrt(n_rest * p * (1 - p))
    # a draw that does not look at the gradients: the same rate above and below the rest's median
    s_rest, kept_rest = np.abs(g * h)[~top], (cls == GOSS_REST)[~top]
    hi = s_rest > np.median(s_rest)
    assert abs(kept_rest[hi].mean() - kept_rest[~hi].mean()) < 6 * np.sqrt(p * (1 - p) / (n_rest / 2) * 2)
    assert np.array_equal(mask, (cls != GOSS_OUT).astype(np.float32))
    assert count == (cls != GOSS_OUT).sum()
    mult = np.where(cls == GOSS_REST, np.float32((1 - a) / b), np.float32(1))
    assert np.array_equal(gs, g * mult) and np.array_equal(hs, h * mult)


def test_ties_at_the_threshold_are_kept():
    g = np.repeat(np.float32([3.0, 2.0, 1.0]), 1000)
    cls = np.asarray(draw(g, np.ones_like(g))[0])     # k = 600 falls inside the first tie
    assert (cls == GOSS_TOP).sum() == 1000 and np.all(cls[:1000] == GOSS_TOP)


def test_the_draw_depends_on_seed_and_iteration_alone():
    g, h = grads()
    g2, h2 = grads(seed=1)
    base = np.asarray(draw(g, h)[0])
    assert np.array_equal(base, np.asarray(draw(g, h)[0]))
    # other gradients, same (seed, iteration): the same uniform stream under another top set
    other = np.asarray(draw(g2, h2)[0])
    both_rest = (base != GOSS_TOP) & (other != GOSS_TOP)
    assert np.array_equal(base[both_rest], other[both_rest])
    for changed in (dict(it=13), dict(seed=4)):
        moved = np.asarray(draw(g, h, **changed)[0])
        assert np.array_equal(moved == GOSS_TOP, base == GOSS_TOP)
        assert not np.array_equal(moved, base)


def test_multiclass_scores_are_summed_over_classes():
    g, h = grads(classes=3)
    cls, mask, gs, hs, _ = (np.asarray(x) for x in draw(g, h))
    assert np.array_equal(cls == GOSS_TOP, numpy_top(np.abs(g * h).sum(axis=1), A))
    mult = np.where(cls == GOSS_REST, np.float32(8), np.float32(1))[:, None]
    assert gs.shape == g.shape and np.array_equal(gs, g * mult) and np.array_equal(hs, h * mult)


def test_the_host_face_is_the_same_function():
    g, h = grads()
    cls = np.asarray(draw(g, h)[0])
    mask, mult = goss_sample_np(cfg(), g, h, 12)
    assert mask.dtype == mult.dtype == np.float32
    assert np.array_equal(mask, (cls != GOSS_OUT).astype(np.float32))
    assert np.array_equal(mult, np.where(cls == GOSS_REST, np.float32(8), np.float32(1)))


def test_a_rows_subset_draws_what_a_run_on_those_rows_would():
    g, h = grads()
    rows = np.sort(np.random.default_rng(5).choice(N, size=N // 3, replace=False))
    mask, mult = goss_sample_np(cfg(), g, h, 12, rows=rows)
    sub_mask, sub_mult = goss_sample_np(cfg(), g[rows], h[rows], 12)
    assert np.array_equal(mask[rows], sub_mask) and np.array_equal(mult[rows], sub_mult)
    outside = np.setdiff1d(np.arange(N), rows)
    assert not mask[outside].any() and np.all(mult[outside] == 1)


@pytest.mark.parametrize("it, kw, active", [
    (9, {}, False), (10, {}, True),                    # 1/learning_rate unsampled iterations
    (1, dict(learning_rate=0.5), False), (2, dict(learning_rate=0.5), True),
    (50, dict(top_rate=0.7, other_rate=0.3), False),   # a + b >= 1: nothing to thin
    (50, dict(top_rate=0.7, other_rate=0.29), True)])
def test_warm_up_and_full_rates_draw_nothing(it, kw, active):
    g, h = grads(n=2000)
    assert (goss_rates(cfg(**kw), it) is not None) == active
    assert (goss_sample_np(cfg(**kw), g, h, it) is not None) == active


def test_four_row_shards_draw_the_same_classes():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("JAX was up with fewer than four CPU devices")
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n = 40_000
    g, h = grads(n=n)
    one = draw(g, h)
    rows = NamedSharding(Mesh(np.array(devs[:4]), ("workers",)), P("workers"))
    four = draw(jax.device_put(g, rows), jax.device_put(h, rows))
    assert four[0].sharding.is_equivalent_to(rows, 1) and four[1].sharding.is_equivalent_to(rows, 1)
    for x, y in zip(one, four):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_the_sampler_names_its_scope():
    import re
    x = jnp.zeros((4096,), jnp.float32)
    text = goss_sample.lower(x, x, 11, top_rate=A, other_rate=B,
                             bagging_seed=SEED).compile().as_text()
    # the innermost lgbm. component of every op_name, as tests/test_phase_scopes.py reads them
    named = [[c for c in op.split("/") if c.startswith("lgbm.")]
             for op in re.findall(r'op_name="([^"]*)"', text)]
    assert {c[-1] for c in named if c} == {"lgbm.goss.sample"}


def _train_goss(trees):
    rng = np.random.RandomState(7)
    X = rng.randn(3000, 5)
    y = (X[:, 0] + X[:, 1] ** 2 > 0.8).astype(float)
    params = {"objective": "binary", "boosting": "goss", "learning_rate": 0.5, "num_leaves": 7,
              "verbosity": -1}
    bst = lgb.Booster(params=params, train_set=lgb.Dataset(X, y, params=params))
    samples = []
    for _ in range(trees):
        bst.update()
        samples.append(bst._gbdt.last_sample())
    return bst, samples


def test_the_booster_names_the_draw_in_the_trace_and_the_record():
    tr = telemetry.global_tracer
    tr.enable()
    tr.clear()
    try:
        bst, samples = _train_goss(5)
        names = [e["name"] for e in tr.events()]
    finally:
        tr.disable()
        tr.clear()
    assert names.count("train/iter/sample") == 4      # the first update is train/first_update
    assert "train/first_update/sample" in names
    snap = bst.train_record.snapshot()
    assert snap["phase_calls"]["sample"] == 5
    bags = [r["sampled_rows"] for r in snap["trees"]]
    assert bags[:2] == [3000, 3000] and all(600 <= b < 3000 for b in bags[2:])
    # last_sample(): None in the warm-up, then the newest draw's classes, on the device
    assert samples[0] is None and samples[1] is None
    assert all(isinstance(s, jax.Array) and s.dtype == jnp.uint8 and s.shape == (3000,)
               for s in samples[2:])
    assert bags[-1] == int((np.asarray(samples[-1]) != GOSS_OUT).sum())
    # the mask the grower got is the draw's, and the warm-up's ones are made once
    assert np.array_equal(np.asarray(bst._gbdt._last_sample_mask) > 0,
                          np.asarray(samples[-1]) != GOSS_OUT)
    assert bst._gbdt._all_rows_mask() is bst._gbdt._all_rows_mask()


def test_the_sampling_hook_copies_nothing_to_the_host():
    """``GOSS._prepare_iter_sampling`` hands device arrays to one jitted
    program: no ``device_get``, no numpy."""
    import inspect
    from lightgbm_tpu.models.boosting import GOSS
    src = inspect.getsource(GOSS._prepare_iter_sampling)
    assert "device_get" not in src and "np." not in src and "goss_sample(" in src
    assert "Philox" not in inspect.getsource(gbdt.goss_sample_np)
