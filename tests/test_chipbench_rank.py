"""The benchmark's learning-to-rank cell at a tiny size, in the tier-1 suite:
``objective=lambdarank`` on q8 through ``lgb.Dataset(blocks, label,
group=)``, the driver ``train_loop_rank`` end to end through the device stub,
judged ``correct`` by ``chipbench/reference_rank.py`` (float64 lambdas query
by query, the trees walked in float64); the truncation-29 control and a
``lambdarank_norm`` fault read ``correct`` false by ``lambda_gap``; a program
whose record states no query layout ends before any data is made.  With them
the manifest with seven cells, the generator, and the three readers of the
objective's layer."""

import json
import os

import numpy as np
import pytest

from chipbench import datagen_rank, reference_rank, roofline, roofline_rank, run, validate
from chipbench import manifest as mf
from chipbench.facts import Facts
from chipbench.tests import helpers
from chipbench.tools import readings_rank

SEED = 2**31 + 41
CELL = "istella-rank-q8.train"
RANK_METRICS = ("rank_grad_device_ms_per_tree", "rank_pair_slot_share", "rank_grad_roofline")
TINY_DATA = {
    "generator": "istella_letor_like", "rows": 4000, "queries": 40, "features": 12,
    "holdout_queries": 24, "length_mean": 100.0, "length_sigma": 1.0, "length_min": 2,
    "length_max": 600, "query_features": 2, "count_features": 4,
    "count_zero_share": [0.5, 0.6, 0.7, 0.8], "query_shift": 0.5,
    "label_shares": [0.6, 0.2, 0.1, 0.07, 0.03], "no_relevant_share": 0.1,
    "query_effect": 0.5, "noise": 0.5, "interaction": 0.3, "weights_seed": 7,
}
TINY_MIX = dict(helpers.TINY_MIX, name="tiny-steady-rank", driver="train_loop_rank",
                sampled_queries=12)
TINY_MIX.pop("score_sample_blocks")


def tiny_config() -> dict:
    cfg = helpers.tiny_config("tiny-rank", True)
    cfg["data"] = dict(TINY_DATA)
    cfg["params"].update(objective="lambdarank", min_data_in_leaf=0,
                         min_sum_hessian_in_leaf=1.0, lambdarank_truncation_level=30,
                         lambdarank_norm=True, min_data_in_bin=1)
    ref = mf.load_json(os.path.join(helpers.REPO, "chipbench/configs/istella-letor-rank-q8.json"))
    cfg["control"] = ref["control"]
    # at 4,000 rows int8 levels read about 0.07 at the median node
    cfg["limits"].update(lambda_gap=ref["limits"]["lambda_gap"], split_gain_median_gap=0.3,
                         split_gain_gap=2.0)
    return cfg


def make_root(tmp: str) -> str:
    root = helpers.make_root(tmp, quantized=True)
    extra = os.path.join(root, "extrabench")
    cfg = tiny_config()
    with open(os.path.join(extra, "configs", "tiny-rank.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(extra, "workloads", "tiny-steady-rank.json"), "w") as fh:
        json.dump(TINY_MIX, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({"name": "tiny-rank", "source": "test", "reduced": [],
                                "why": "test", "file": "extrabench/configs/tiny-rank.json"})
    manifest["workloads"].append({"name": "tiny-rank.train", "config": "tiny-rank",
                                  "traffic": "tiny-steady-rank", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"].append("tiny-rank.train")
    with open(path, "w") as fh:
        json.dump(manifest, fh)
    return root


@pytest.fixture(autouse=True)
def cpu_stands_in(monkeypatch):
    monkeypatch.setattr(run, "find_device", lambda chips: dict(helpers.CPU_DEVICE))
    real = roofline.load_peaks
    monkeypatch.setattr(roofline, "load_peaks", lambda kind, path=None: real("TPU v5 lite"))
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def drive(tmp_path, capsys, trace=0):
    root = make_root(str(tmp_path))
    rc = run.main(["--workload", "tiny-rank.train", "--seed", str(SEED), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_the_manifest_with_seven_cells_passes():
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    assert len(m["configs"]) >= 7 and len(m["workloads"]) >= 7
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["criteo-q8-dp4.train"]
    cell = mf.find_named(m["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("istella-letor-rank-q8", "train-steady-rank", 1)
    mine = {x["name"] for x in mf.metrics_for(m, CELL, "per_layer")}
    assert set(RANK_METRICS) <= mine and "tree_step_mfu" in mine
    assert not {n for n in mine if n.startswith(("efb_", "goss_", "cat_", "mesh_"))}
    for name in RANK_METRICS:
        entry = mf.find_named(m["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL] and entry["layer"] == "objective"
        assert os.path.isfile(mf.metric_file(helpers.REPO, m, name))
    mix = mf.load_json(mf.mix_file(helpers.REPO, m, cell["traffic"]))
    assert os.path.isfile(mf.driver_file(helpers.REPO, m, mix["driver"]))
    assert (mix["warmup_trees"], mix["min_window_trees"], mix["auc_trees"],
            mix["predict_chunk_rows"], mix["trace_trees"]) == (2, 4, 6, 16384, 3)
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/istella-letor-rank-q8.json")
    assert cfg["reduced"] == ["num_trees", "holdout_queries"]
    d, p = cfg["data"], cfg["params"]
    assert (d["rows"], d["queries"], d["features"]) == (7_325_625, 23_219, 220)
    assert (d["rows"], d["queries"]) == (cfg["upstream"]["train_rows"],
                                         cfg["upstream"]["train_queries"])
    assert (p["objective"], p["num_leaves"], p["learning_rate"], p["min_data_in_leaf"],
            p["min_sum_hessian_in_leaf"], p["lambdarank_truncation_level"]) == \
        ("lambdarank", 255, 0.1, 0, 100, 30)
    assert cfg["control"]["params"] == {"lambdarank_truncation_level": 29}
    assert "lambda_gap" in cfg["limits"]


def test_the_generator_makes_the_queries_exactly():
    spec = datagen_rank.RankSpec(TINY_DATA)
    assert spec.lengths.sum() == 4000 and len(spec.lengths) == 40
    assert spec.lengths.min() >= 2 and spec.lengths.max() <= 600
    assert spec.block_range(0)[0] == 0 and spec.block_range(spec.blocks - 1)[1] == 4000
    xs, y = datagen_rank.training_blocks(spec, SEED)
    assert sum(len(x) for x in xs) == 4000 and xs[0].dtype == np.float32
    again = datagen_rank.block(spec, SEED, spec.blocks - 1)
    assert np.array_equal(again[0], xs[-1])
    assert not np.array_equal(datagen_rank.block(spec, SEED + 1, 0)[0], xs[0])
    # query-level columns are constant within a query
    x = np.concatenate(xs)
    b = spec.boundaries
    assert all((x[b[q]:b[q + 1], :2] == x[b[q], :2]).all() for q in range(40))
    assert set(np.unique(y)) <= {0, 1, 2, 3, 4}
    xh, yh, lh = datagen_rank.holdout(spec, SEED)
    assert len(xh) == len(yh) == lh.sum() == spec.holdout_rows and len(lh) == 24


@pytest.mark.parametrize("rows, queries", [(7_325_625, 23_219), (1_000, 3)])
def test_the_lengths_sum_exactly_inside_the_clip(rows, queries):
    rng = np.random.default_rng(3)
    lengths = datagen_rank.adjust_total(
        datagen_rank.lognormal_lengths(rng, queries, 315.5, 1.0, 2, 3200), rows, 2, 3200, rng)
    assert lengths.sum() == rows and len(lengths) == queries
    assert lengths.min() >= 2 and lengths.max() <= 3200


def test_a_run_on_the_cpu_is_correct_and_states_its_layout(tmp_path, capsys):
    line, err = drive(tmp_path, capsys, trace=1)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {"leaf_count_diff", "leaf_value_gap", "split_gain_gap",
                                   "split_gain_median_gap", "lambda_gap", "train_score_gap",
                                   "heldout_pred_gap"}
    assert line["checks"]["lambda_gap"]["value"] < 1e-4
    notes = line["notes"]
    layout = notes["rank_layout"]
    assert layout["queries"] == 40 and layout["truncation_level"] == 30
    spec = datagen_rank.RankSpec(TINY_DATA)
    assert layout["pairs_counted"] == reference_rank.pairs_visited(spec.lengths, 30)
    share = line["metrics"]["rank_pair_slot_share"]["value"]
    assert share == pytest.approx(layout["pairs_counted"] / layout["pair_slots"])
    assert 0.3 < share <= 1.0
    assert 0.5 < line["metrics"].get("heldout_auc_6", {"value": 0.6})["value"] <= 1.0
    assert 0.0 < notes["ndcg_10"] <= 1.0
    assert max(notes["sampled_query_lengths"]) == spec.lengths.max()
    assert min(notes["sampled_query_lengths"]) == spec.lengths.min()


def test_the_control_and_the_fault_are_refused(tmp_path, capsys):
    """The readings tool on one seed: the program as stated passes, truncation
    29 and ``lambdarank_norm`` off fail by ``lambda_gap``, judged against the
    reference at the configuration's own parameters."""
    root = make_root(str(tmp_path))
    assert readings_rank.main(["--seeds", str(SEED), "--config", "tiny-rank",
                               "--control-seeds", "1", "--trees", "3"], root=root) == 0
    recs = {r["kind"]: r["numbers"] for r in
            map(json.loads, capsys.readouterr().out.strip().splitlines())}
    assert set(recs) == {"program", "control", "fault_norm_dropped"}
    limits = tiny_config()["limits"]
    ok = lambda numbers: reference_rank.judge(numbers, limits)[0]
    assert ok(recs["program"]), recs["program"]
    for kind in ("control", "fault_norm_dropped"):
        assert not ok(recs[kind])
        assert recs[kind]["lambda_gap"] > 30 * recs["program"]["lambda_gap"], recs
        assert recs[kind]["lambda_gap"] > limits["lambda_gap"]


def test_a_program_without_the_layout_record_ends_before_the_data(tmp_path, capsys,
                                                                     monkeypatch):
    from lightgbm_tpu.objective import rank
    monkeypatch.setattr(rank.LambdarankNDCG, "record", lambda self: {})
    root = make_root(str(tmp_path))
    with pytest.raises(SystemExit) as info:
        run.main(["--workload", "tiny-rank.train", "--seed", str(SEED), "--seconds", "0.5",
                  "--trace", "0"], root=root)
    assert "query layout" in str(info.value)


def test_the_readers_of_the_objective_layer():
    m = mf.load_manifest(helpers.REPO)
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/istella-letor-rank-q8.json")
    peaks = roofline.load_peaks("TPU v5 lite")
    read = lambda name, facts: mf.load_module(mf.metric_file(helpers.REPO, m, name)).read(facts)
    layout = {"pair_slots": 311_083_584, "pairs_counted": 209_005_099}
    facts = Facts(cfg, helpers.CPU_DEVICE, peaks, {"rank_layout": layout})
    assert read("rank_pair_slot_share", facts) == pytest.approx(0.67186, abs=1e-4)
    assert read("rank_pair_slot_share", Facts(cfg, helpers.CPU_DEVICE, peaks, {})) is None
    # no trace: the two device readers find nothing
    assert read("rank_grad_device_ms_per_tree", facts) is None
    assert read("rank_grad_roofline", facts) is None
    # 16 bytes a row and 4 a query at 819 GB/s: 0.143 ms for the cell's shapes
    floor = roofline_rank.gradient_floor(7_325_625, 23_219, peaks)
    assert floor == pytest.approx((7_325_625 * 16 + 23_219 * 4) / 819e9)
