"""A tree grown for a booster that samples rows contracts only its in-bag
rows (ISSUE 33): an out-of-bag row carries channel -1 at every full-data
histogram pass, the ramp's verify pass and the root pass included, and
every such pass goes through the row compaction.  The tree, and every
row's leaf, are those of a grower that contracts every row.  A booster
that never samples keeps the program it had.  Everything here runs the
Pallas kernels interpreted, on the CPU: results and counts, never a
speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import histogram_pallas as hp

KR = hp.DEFAULT_ROW_BLOCK
N, N_PAD = 9000, 12288           # three row blocks
N_TREE = 73000                   # eighteen: a pass's ragged block is little
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "min_data_in_leaf": 5, "tree_grow_mode": "wave",
          "tpu_histogram_impl": "pallas", "tpu_pallas_pipeline": "dma",
          "verbosity": -1}
Q8 = {"use_quantized_grad": True, "num_grad_quant_bins": 254,
      "quant_train_renew_leaf": True}
ARITH = {"q8": Q8, "exact": {}}
# W = 4 of 15 leaves: the speculative ramp and its verify pass; the
# default wave (14) starts from the root pass
FIRST_PASS = {"ramp": {"tpu_wave_size": 4}, "root": {}}
# what makes a booster one that samples (``Config.samples_rows``)
SAMPLING = {"goss": {"boosting": "goss"},
            "bagging": {"bagging_freq": 1, "bagging_fraction": 0.3}}


def _data(n=N):
    rng = np.random.RandomState(11)
    X = rng.randn(n, 6)
    y = (X[:, 0] + X[:, 1] ** 2 + 0.3 * rng.randn(n) > 0.8).astype(float)
    return X, y


def _learner(params, n=N):
    """``(learner, row-major bins on the device)`` of a booster."""
    X, y = _data(n)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, y, params=params))
    return bst._gbdt.learner, bst._gbdt.X_dev


def _sample(how, share=0.3, n=N, shard=0):
    """``(grad, hess, mask)`` of one sampled tree.  ``goss``: the rows of
    largest |g| at weight 1, a draw of the others at weight 8, the
    weights folded into the gradients as ``goss_sample`` leaves them;
    ``bagging``: a plain 0/1 draw; with ``shard`` none of the first
    ``shard`` rows and half as many of the next ``shard`` as elsewhere."""
    rng = np.random.RandomState(5)
    y = _data(n)[1]
    p = 1.0 / (1.0 + np.exp(-0.6 * rng.randn(n)))
    grad = (p - y).astype(np.float32)
    hess = (p * (1.0 - p)).astype(np.float32)
    if how == "goss":
        top = np.abs(grad) >= np.sort(np.abs(grad))[-int(n * share * 2 / 3)]
        rest = ~top & (rng.rand(n) < share / 3 / (1 - share * 2 / 3))
        mult = np.where(rest, 8.0, 1.0).astype(np.float32)
        grad, hess, mask = grad * mult, hess * mult, top | rest
    else:
        mask = rng.rand(n) < share
    if shard:
        mask[:shard] = False
        mask[shard:2 * shard] &= rng.rand(shard) < 0.5
    return (jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask.astype(np.float32)))


def _grow(learner, X_dev, sample):
    return jax.device_get(learner.train(
        X_dev, *sample, quant_key=jax.random.PRNGKey(7)))


def _rows(tree):
    """Rows the tree's counted passes looped over, all shards."""
    units = np.asarray(tree.hist_rows_contracted)
    return int((units[:, 0] * units[:, 1]).sum())


def _assert_same_tree(got, want, exact_bits):
    for name in type(want)._fields:
        if name in ("hist_rows_contracted", "pass_log", "ramp_sample"):
            continue      # a shard's own counts: of the rows it looped over
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        if exact_bits or not np.issubdtype(b.dtype, np.floating):
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            # the same f32 products met in other row blocks
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5,
                                       err_msg=name)


def _looped(mask_pad, kb):
    """Rows the leaf kernel loops over behind a compaction of the rows
    with ``mask_pad``: every block's count rounded up to whole tiles."""
    total = sum(-(-int(mask_pad[lo:lo + kb].sum()) // 128) * 128
                for lo in range(0, mask_pad.shape[0], kb))
    return max(1, -(-total // KR)) * KR


@pytest.mark.parametrize("first", list(FIRST_PASS))
@pytest.mark.parametrize("how", list(SAMPLING))
@pytest.mark.parametrize("kind", list(ARITH))
def test_sampled_grower_grows_the_unsampled_growers_tree(kind, how, first):
    base = {**PARAMS, **ARITH[kind], **FIRST_PASS[first]}
    plain, X_dev = _learner(base, N_TREE)
    sampled, _ = _learner({**base, **SAMPLING[how]}, N_TREE)
    assert not plain._grow_kwargs["sampled"]
    assert sampled._grow_kwargs["sampled"]
    assert {k: v for k, v in sampled._grow_kwargs.items() if k != "sampled"} \
        == {k: v for k, v in plain._grow_kwargs.items() if k != "sampled"}
    sample = _sample(how, n=N_TREE)
    want = _grow(plain, X_dev, sample)
    got = _grow(sampled, X_dev, sample)
    assert int(want.num_leaves) > 8 and int(want.hist_passes) > 2
    # every field, and the leaf of EVERY row, in the bag or not
    assert got.row_leaf.shape == (N_TREE,)
    out = np.asarray(sample[2]) == 0
    assert len(np.unique(np.asarray(got.row_leaf)[out])) > 4
    _assert_same_tree(got, want, exact_bits=kind == "q8")
    # the dense first pass alone is a third of the plain tree's rows
    assert _rows(want) >= hp.pad_rows(N_TREE)
    assert _rows(got) <= 0.4 * _rows(want)
    # the same passes, pass by pass: kinds and leaves built; the sampled
    # grower's channels hold the bag's rows alone, in every pass
    log_w, log_g = np.asarray(want.pass_log)[0], np.asarray(got.pass_log)[0]
    passes = int(want.hist_passes)
    np.testing.assert_array_equal(log_g[:, :2], log_w[:, :2])
    assert (log_g[:passes, 3] < log_w[:passes, 3]).all()
    assert log_g[0, 3] == int(np.asarray(sample[2]).sum())


@pytest.mark.parametrize("first", list(FIRST_PASS))
@pytest.mark.parametrize("kind", list(ARITH))
def test_first_pass_loops_over_the_bag_alone(kind, first):
    """A tree in which no split is worth the gain asked for: the counter
    is its first pass's trip count (the ramp's grower then looks once
    more, over no row: the one block a kernel always fetches)."""
    base = {**PARAMS, **ARITH[kind], **FIRST_PASS[first],
            "min_gain_to_split": 1e9}
    sample = _sample("bagging")
    mask_pad = np.pad(np.asarray(sample[2]) > 0, (0, N_PAD - N))
    for params, rows in (
            (base, N_PAD),
            ({**base, **SAMPLING["bagging"]},
             _looped(mask_pad, hp._compact_block(N_PAD, 8)))):
        learner, X_dev = _learner(params)
        tree = _grow(learner, X_dev, sample)
        assert int(tree.num_leaves) == 1
        assert int(tree.hist_passes) == 1 + (first == "ramp")
        assert _rows(tree) == rows + (first == "ramp") * KR
    assert rows == -(-int(mask_pad.sum()) // KR) * KR < N_PAD


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def _kernels(params):
    """The grower's kernels in program order, at the call
    ``learner.train`` makes."""
    learner, X_dev = _learner(params)
    compiled, calls = learner._grow, []
    learner._grow = lambda *a, **k: (calls.append((a, k)),
                                     compiled(*a, **k))[1]
    learner.train(X_dev, *_sample("bagging"),
                  quant_key=jax.random.PRNGKey(7))
    (a, k), = calls
    return _pallas_names(jax.make_jaxpr(compiled)(*a, **k).jaxpr, [])


def _pinned(leaves, w, first, renew):
    """``(kernels, dense pass, compacted pass)`` of the unsampled grower
    as commit 95b945c (before this change) traced them: a DENSE first
    pass over the N_PAD rows, then plan + compaction + a leaf kernel over
    the compacted arrays (one row block of padding more) in the wave body
    and in the endgame."""
    dense = f"lgbm_hist_{leaves}_dma_f8_fc8_b64_g4_kr4096_n{N_PAD}"
    route = f"lgbm_wave_row_update_dma_w{w}_f6_kr4096_n{N_PAD}"
    compacted = [f"lgbm_hist_compact_plan_s512_r24_n{N_PAD}",
                 f"lgbm_hist_compact_dma_f8_fc8_s512_kb4096_n{N_PAD}",
                 f"lgbm_hist_{leaves}_dma_f8_fc8_b64_g4_kr4096_n{N_PAD + KR}"]
    ramp = [dense, route, dense, route,      # two provisional passes
            route, route,                    # every row through them
            dense, route]                    # the verify pass
    body = compacted + [route] * 3           # wave; endgame: flush, trial
    tail = compacted + [route] * 2
    return ((ramp if first == "ramp" else [dense, route]) + body + tail +
            ([f"lgbm_hist_single_dma_f8_b256_g1_kr4096_n{N_PAD}"]
             if renew else [])), dense, compacted


@pytest.mark.parametrize("kind,first", [("q8", "ramp"), ("exact", "ramp"),
                                        ("q8", "root")])
def test_unsampled_grower_keeps_its_kernels(kind, first):
    leaves = "leaves_q8" if kind == "q8" else "leaves"
    w = 4 if first == "ramp" else 14
    base = {**PARAMS, **ARITH[kind], **FIRST_PASS[first]}
    want, dense, compacted = _pinned(leaves, w, first, renew=kind == "q8")
    assert _kernels(base) == want
    # the sampled grower: the same passes, every one of them behind a
    # compaction (the ramp's provisional passes on its subsample too)
    got = _kernels({**base, **SAMPLING["bagging"]})
    assert got == [k for name in want
                   for k in (compacted if name == dense else [name])]


@pytest.mark.parametrize("kind", list(ARITH))
def test_row_shards_with_unequal_bags_grow_the_serial_tree(
        kind, dma_everywhere):
    """Four row shards: none of the first one's rows in the bag, and the
    others hold unequal shares of it."""
    base = {**PARAMS, **ARITH[kind], **SAMPLING["bagging"],
            "stochastic_rounding": False}
    n = 15000                     # of 4 x 4096: the last shard is ragged
    sample = _sample("bagging", share=0.5, n=n, shard=KR)
    mask_pad = np.pad(np.asarray(sample[2]) > 0, (0, 4 * KR - n))
    counts = mask_pad.reshape(4, -1).sum(axis=1)
    assert counts[0] == 0 and len(set(counts.tolist())) == 4
    one, X_dev = _learner(base, n)
    want = _grow(one, X_dev, sample)
    mesh, X_mesh = _learner({**base, "tree_learner": "data",
                             "num_devices": 4}, n)
    assert mesh.mesh is not None and mesh._grow_kwargs["sampled"]
    got = _grow(mesh, X_mesh, sample)
    assert int(want.num_leaves) > 8
    # (float fields to f32 tolerance in q8 too: renewal sums f32
    # gradients shard by shard)
    _assert_same_tree(got, want, exact_bits=False)
    # each shard compacted its own rows: the empty one looped over the
    # one block the kernel's pipeline always fetches, pass after pass
    units = np.asarray(got.hist_rows_contracted)
    assert units.shape == (4, 2)
    assert units[0, 0] * units[0, 1] == int(got.hist_passes) * KR
    assert _rows(got) < _rows(want) + 4 * int(got.hist_passes) * KR


# ---------------------------------------------------------------------------
# the counter's per-layer metric (chipbench/layer_metrics)
# ---------------------------------------------------------------------------

def _facts(trees, passes):
    from chipbench.facts import Facts
    facts = Facts({"data": {"rows": 1000}}, {}, {}, {"hist_passes": passes})
    facts.program_snapshot = {"trees": trees}
    return facts


@pytest.mark.parametrize("trees,passes,want", [
    # two warm-up trees the window leaves out, then 3 + 5 passes
    ([{"hist_passes": 9, "hist_rows_contracted": 9000}] * 2 +
     [{"hist_passes": 3, "hist_rows_contracted": 1200},
      {"hist_passes": 5, "hist_rows_contracted": 1200}], [3, 5], 0.3),
    # a program that keeps no such counter (before PR 31)
    ([{"hist_passes": 3}, {"hist_passes": 5}], [3, 5], None),
    # a record that is not the window's
    ([{"hist_passes": 4, "hist_rows_contracted": 1200}], [3], None),
    ([], [], None),
])
def test_hist_rows_contracted_share_reader(trees, passes, want):
    from chipbench import manifest as mf
    root = mf.repo_root()
    manifest = mf.load_manifest(root)
    entry = mf.find_named(manifest["per_layer"],
                          "hist_rows_contracted_share", "metric")
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "grower" and entry["better"] == "lower"
    # the four cells PR 33 gave it, and those that later PRs append
    assert entry["workloads"][:4] == [
        "criteo-q8.train", "criteo-exact.train", "criteo-q8-dp4.train",
        "criteo-q8-goss.train"]
    reader = mf.load_module(mf.metric_file(root, manifest, entry["name"]))
    got = reader.read(_facts(trees, passes))
    assert got is None if want is None else got == pytest.approx(want)
