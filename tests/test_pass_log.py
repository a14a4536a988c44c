"""The wave grower's own log of a tree's counted histogram passes (ISSUE 36):
``GrownTree.pass_log`` on the device, ``TrainRecord.snapshot()["trees"][i]
["passes"]`` on the host, and the per-tree ``ramp_sample_rows``.  One entry a
counted pass, written where the pass is counted, from counts the pass already
makes (the compaction plan's, ops/histogram_pallas.py).  Everything here runs
the Pallas kernels interpreted, on the CPU: counts and identities, never a
speed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.learner import wave
from lightgbm_tpu.ops import histogram_pallas as hp
from lightgbm_tpu.telemetry.train_record import _passes

KR = hp.DEFAULT_ROW_BLOCK
N = 9000                         # three row blocks on one device
PARAMS = {"objective": "binary", "num_leaves": 15, "max_bin": 63,
          "min_data_in_leaf": 5, "tree_grow_mode": "wave",
          "tpu_histogram_impl": "pallas", "tpu_pallas_pipeline": "dma",
          "verbosity": -1}
Q8 = {"use_quantized_grad": True, "num_grad_quant_bins": 254,
      "quant_train_renew_leaf": True}
RAMP = {"tpu_wave_size": 4}      # W = 4 of 15 leaves: ramp, waves, endgame
# set-up -> (parameters, trees, chips); GOSS samples from tree 1 / lr on
SETUPS = {
    "q8": ({**PARAMS, **Q8, **RAMP}, 3, 1),
    "exact": (PARAMS, 2, 1),     # the default wave starts from the root
    "goss": ({**PARAMS, **Q8, **RAMP, "boosting": "goss",
              "learning_rate": 0.5}, 4, 1),
    "cat": ({**PARAMS, **Q8, "categorical_feature": [5]}, 2, 1),
    "shards4": ({**PARAMS, **Q8, **RAMP, "tree_learner": "data",
                 "num_devices": 4}, 2, 4),
}


def _data(n=N, cat=False):
    rng = np.random.RandomState(11)
    X = rng.randn(n, 6)
    if cat:
        X[:, 5] = rng.randint(0, 12, n)
    y = (X[:, 0] + X[:, 1] ** 2 + 0.3 * (X[:, 5] % 3 if cat else 0)
         + 0.3 * rng.randn(n) > 0.8).astype(float)
    return X, y


@pytest.mark.parametrize("setup", list(SETUPS))
def test_pass_log_identities(setup, request):
    params, trees, chips = SETUPS[setup]
    if chips > 1:                # a mesh takes the platform's pipeline
        request.getfixturevalue("dma_everywhere")
    n = N * chips
    X, y = _data(n, cat=setup == "cat")
    data = lgb.Dataset(X, y, params=params, categorical_feature=(
        params.get("categorical_feature", "auto")))
    bst = lgb.train(params, data, trees)
    snap = bst.train_record.snapshot()
    lanes = chips * hp.pad_rows(-(-n // chips))
    sampled = snap["grower"]["sampled"]
    assert sampled == (setup == "goss")
    assert len(snap["trees"]) == trees
    for r in snap["trees"]:
        passes = r["passes"]
        assert len(passes) == r["hist_passes"] > 1
        kinds = [p["kind"] for p in passes]
        assert kinds[0] == wave.PASS_FIRST
        assert kinds.count(wave.PASS_FIRST) == 1
        assert kinds.count(wave.PASS_WAVE) == r["wave_passes"]
        assert kinds.count(wave.PASS_ENDGAME) == r["endgame_passes"]
        assert kinds == sorted(kinds)        # first, waves, endgame
        assert sum(p["rows"] for p in passes) == r["hist_rows_contracted"]
        for p in passes:
            assert 0 < p["active_rows"] <= p["rows"] <= lanes + chips * KR
            assert 0 < p["blocks_active"] <= p["blocks"]
            assert 1 <= p["leaves"] <= 14
        first = passes[0]
        in_bag = r["sampled_rows"] < n
        assert in_bag == (setup == "goss" and r["iteration"] >= 2)
        if sampled:
            # compacted: the lanes with a channel are the bag's rows
            assert first["active_rows"] == r["sampled_rows"]
            assert (first["rows"] < lanes) == in_bag
        else:
            # dense: every lane looped over, every block counted active
            assert first["rows"] == first["active_rows"] == lanes
            assert first["blocks_active"] == first["blocks"]
        assert all(p["blocks"] == first["blocks"] for p in passes)
        if setup == "cat":       # neither ramp nor endgame: root + waves
            assert r["endgame_passes"] == r["ramp_committed"] == 0
            assert (r["ramp_sample_rows"], r["ramp_sample_lanes"]) == (0, 0)
            assert first["leaves"] == 1
            assert 1 + sum(p["leaves"] for p in passes[1:]) == r["num_leaves"]
        elif setup != "exact":   # the ramp: the stride is 1 at this size
            assert first["leaves"] == 4 and r["ramp_committed"] <= 3
            assert r["ramp_sample_lanes"] == lanes
            assert r["ramp_sample_rows"] == (r["sampled_rows"] if sampled
                                             else lanes)
    if setup == "goss":
        assert any(r["sampled_rows"] < n for r in snap["trees"])


def _sampled_ramp_learner(n):
    """A q8 grower built for a booster that samples rows, its ramp's
    subsample cut to 4,096 lanes (the stride is then n_pad // 4096)."""
    params = {**PARAMS, **Q8, **RAMP, "bagging_freq": 1,
              "bagging_fraction": 0.3}
    X, y = _data(n)
    bst = lgb.Booster(params=params,
                      train_set=lgb.Dataset(X, y, params=params))
    learner = bst._gbdt.learner
    assert learner._grow_kwargs["sampled"]
    learner._grow_kwargs["spec_subsample"] = 4096
    learner._grow = learner.build_grow_fn()
    return learner, bst._gbdt.X_dev


def test_emptied_blocks_and_the_strides_bag_count():
    """A bag that leaves whole compaction blocks empty lowers
    ``blocks_active`` by exactly that many, and ``ramp_sample_rows`` is
    the in-bag count of the ramp's stride."""
    n = 73000                    # eighteen row blocks: n_pad 73,728
    learner, X_dev = _sampled_ramp_learner(n)
    n_pad = hp.pad_rows(n)
    kb = hp._compact_block(n_pad, 8)
    stride = n_pad // 4096
    rng = np.random.RandomState(5)
    y = _data(n)[1]
    p = 1.0 / (1.0 + np.exp(-0.6 * rng.randn(n)))
    grad, hess = (p - y).astype(np.float32), (p * (1 - p)).astype(np.float32)
    full = rng.rand(n) < 0.3
    seen = {}
    for emptied in (0, 2):
        mask = full.copy()
        mask[:emptied * kb] = False
        tree = jax.device_get(learner.train(
            X_dev, jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask.astype(np.float32)),
            quant_key=jax.random.PRNGKey(7)))
        passes = _passes(tree.pass_log, tree.hist_rows_contracted,
                         int(tree.hist_passes))
        assert len(passes) == int(tree.hist_passes) > 2
        mask_pad = np.pad(mask, (0, n_pad - n))
        first = passes[0]
        assert first["active_rows"] == int(mask.sum())
        assert first["blocks"] == n_pad // kb
        assert first["blocks_active"] == sum(
            bool(mask_pad[lo:lo + kb].any()) for lo in range(0, n_pad, kb))
        assert all(q["blocks_active"] <= q["blocks"] - emptied
                   for q in passes)
        seen[emptied] = first["blocks_active"]
        assert tree.ramp_sample.tolist() == [
            [int(mask_pad[::stride][:4096].sum()), 4096]]
    assert seen[0] == n_pad // kb and seen[2] == seen[0] - 2


def test_a_log_shorter_than_its_tree_sums_the_overflow(monkeypatch):
    """Past the log's length the later passes' counts go into the last
    entry: the looped rows still sum to the tree's, and the row says how
    many passes there were."""
    monkeypatch.setattr(wave, "PASS_LOG_CAP", 3)
    from lightgbm_tpu.learner import serial
    serial._GROW_FN_CACHE.clear()
    params = {**PARAMS, **Q8, **RAMP}
    try:
        X, y = _data()
        bst = lgb.train(params, lgb.Dataset(X, y, params=params), 2)
    finally:
        serial._GROW_FN_CACHE.clear()
    for r in bst.train_record.snapshot()["trees"]:
        assert r["hist_passes"] > 3 == len(r["passes"])
        assert sum(p["rows"] for p in r["passes"]) == r["hist_rows_contracted"]
        assert [p["kind"] for p in r["passes"]] == [0, 1, 2]


def test_growers_without_a_log_report_no_passes():
    X, y = _data(600)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    bst = lgb.train(params, lgb.Dataset(X, y, params=params), 2)
    for r in bst.train_record.snapshot()["trees"]:
        assert r["passes"] == [] and r["hist_passes"] == 0
        assert (r["ramp_sample_rows"], r["ramp_sample_lanes"]) == (0, 0)


def test_kernel_events_are_paired_with_their_passes():
    """``scripts/hist_kernel_events.py``: a counted pass is the kernels at
    the data's full length (plan + compaction + leaf kernel, or a dense
    leaf kernel, in segments where the grower sums that way); the ramp's
    subsample passes and the renewal pass belong to none."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "hist_kernel_events", os.path.join(os.path.dirname(__file__), "..",
                                           "scripts", "hist_kernel_events.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    n = 21250048
    leaf = "lgbm_hist_leaves_q8_dma_f96_fc72_b256_g8_kr4096_n"
    seg = "lgbm_hist_leaves_q8_dma_seg_f64_fc40_b256_g8_kr4096_n"
    compacted = [f"lgbm_hist_compact_plan_s512_r1024_n{n}",
                 f"lgbm_hist_compact_dma_f96_fc72_s512_kb8192_n{n}"]
    names = ([leaf + "528384"] * 2 + [leaf + str(n)]          # ramp, verify
             + (compacted + [leaf + str(n + 8192)]) * 2
             + [f"lgbm_hist_single_dma_f8_b256_g1_kr4096_n{n}"]   # renewal
             + [seg + str(n)] * 3                             # a dense root
             + compacted + [seg + str(n + 8192)] * 3)
    assert mod.pass_of_each(names) == (
        [None, None, 0] + [1] * 3 + [2] * 3 + [None] + [3] * 3 + [4] * 5)
