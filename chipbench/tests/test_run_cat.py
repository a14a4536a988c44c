"""``chipbench.run`` on the integer + categorical cell at a tiny size: the
driver ``train_loop_cat`` end to end through the device stub, judged by
``chipbench.reference_cat``; the control and the four planted faults, each of
which has to read ``correct`` false by the number that is there to catch it; a
program whose record states no grower paths, or counts no categorical splits;
a run whose grower took a path the cell does not describe."""

import functools
import json

import pytest

from chipbench import datagen_ctr, reference_cat, roofline, run, validate
from chipbench import manifest as mf
from chipbench.tests import helpers, helpers_cat

SEED = 2**31 + 9


@pytest.fixture(autouse=True)
def cpu_stands_in(monkeypatch):
    monkeypatch.setattr(run, "find_device", lambda chips: dict(helpers.CPU_DEVICE))
    real = roofline.load_peaks
    monkeypatch.setattr(roofline, "load_peaks", lambda kind, path=None: real("TPU v5 lite"))
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def drive(tmp_path, capsys, trace=0, control=False):
    root = helpers_cat.make_root(str(tmp_path), control=control)
    rc = run.main(["--workload", "tiny-cat.train", "--seed", str(SEED), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def failed(line) -> set:
    return {name for name, c in line["checks"].items() if not c["value"] <= c["limit"]}


def test_a_sound_cat_run_is_correct(tmp_path, capsys):
    line, err = drive(tmp_path, capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    assert set(line["metrics"]) == {"train_iters_per_s", "heldout_auc_6", "setup_s"}
    assert set(line["checks"]) == {
        "leaf_count_diff", "leaf_value_gap", "split_gain_gap", "split_gain_median_gap",
        "train_score_gap", "heldout_pred_gap", "cat_law_violations", "cat_search_gap"}
    assert line["checks"]["leaf_count_diff"] == {"value": 0.0, "limit": 0}
    assert line["checks"]["cat_law_violations"] == {"value": 0.0, "limit": 0}
    notes = line["notes"]
    assert notes["compiles_in_window"] == 0
    assert notes["grower"]["row_update"] == "kernel" and notes["grower"]["any_cat"] is True
    assert not (notes["grower"]["ramp"] or notes["grower"]["endgame"] or notes["grower"]["efb"])
    assert len(notes["cat_splits"]) == line["attempted"] and min(notes["cat_splits"]) > 0
    assert all(c <= n for c, n in zip(notes["cat_splits"], notes["internal_nodes"]))
    assert line["metrics"]["heldout_auc_6"]["value"] > 0.7
    assert err.strip().splitlines()[-1] == "correct True"


def test_a_traced_cat_run_reports_the_metrics_it_can_read(tmp_path, capsys):
    line, _ = drive(tmp_path, capsys, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert 0.2 < m["cat_split_share"]["value"] <= 1.0
    assert m["cat_split_share"]["unit"] == "share"
    notes = line["notes"]
    assert m["cat_split_share"]["value"] == pytest.approx(
        sum(notes["cat_splits"]) / sum(notes["internal_nodes"]))
    assert m["hist_passes_per_tree"]["value"] > 0 and m["bin_find_s"]["value"] > 0
    assert m["endgame_passes_per_tree"]["value"] == 0 and m["ramp_committed_per_tree"]["value"] == 0
    # the CPU trace has no device plane: the readers of device time say nothing
    for name in ("cat_scan_device_ms_per_tree", "row_update_kernel_roofline",
                 "hist_kernel_ms_per_pass", "hist_kernel_roofline"):
        assert name not in m


@pytest.mark.parametrize("fault, caught_by", [
    ("unknown_as_top", "leaf_count_diff"),
    ("onehot_only", "cat_search_gap"),
    ("cut_left_sets", "leaf_count_diff"),
    ("flip_nan", "leaf_count_diff"),
    ("int4_for_int8", "split_gain_median_gap"),
])
def test_a_planted_fault_is_not_correct(tmp_path, capsys, monkeypatch, fault, caught_by):
    if fault != "int4_for_int8":
        monkeypatch.setattr(reference_cat, "compare_run",
                            functools.partial(reference_cat.compare_run, fault=fault))
    line, err = drive(tmp_path, capsys, control=fault == "int4_for_int8")
    assert line["correct"] is False
    assert caught_by in failed(line), line["checks"]
    assert err.strip().splitlines()[-1] == "correct False"
    assert f"check {caught_by}" in err and "FAILED" in err


@pytest.mark.parametrize("lacks, says", [("grower", "no 'grower'"),
                                         ("cat_splits", "counts no cat_splits")])
def test_a_program_without_the_record_ends_before_any_data_is_made(tmp_path, capsys,
                                                                   monkeypatch, lacks, says):
    """The parent of the PR that brought categorical slots to the row-update
    kernel: it would route every row of every wave through an XLA gather."""
    from lightgbm_tpu.telemetry.train_record import TrainRecord
    real = TrainRecord.snapshot

    def snapshot(self):
        snap = real(self)
        if lacks == "grower":
            snap.pop("grower")
        else:
            snap["trees"] = [{k: v for k, v in t.items() if k != "cat_splits"}
                             for t in snap["trees"]]
        return snap

    monkeypatch.setattr(TrainRecord, "snapshot", snapshot)
    monkeypatch.setattr(datagen_ctr, "training_blocks", lambda *a, **k: pytest.fail("data made"))
    root = helpers_cat.make_root(str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "tiny-cat.train", "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=root)
    assert says in str(exc.value.code) and exc.value.code != 0
    assert capsys.readouterr().out.strip() == ""          # no result line


@pytest.mark.parametrize("params, impl", [({"objective": "binary"}, "pallas"),
                                          ({"tpu_histogram_impl": "onehot"}, "onehot")])
def test_the_probe_leaves_no_histogram_variant_to_a_timing_of_64_rows(params, impl):
    """With ``tpu_histogram_impl`` left at "auto" the program times its
    variants on a matrix this small and keeps the winner; where "onehot" wins
    that noise the serial learner takes the partitioned grower, whose record
    states no paths, and the probe would end a sound program's run."""
    from chipbench.drivers import train_loop_cat as tlc
    seen = []

    class Record:
        def snapshot(self):
            return {"grower": {"row_update": "kernel"}, "trees": [{"cat_splits": 1}]}

    class Lgb:
        @staticmethod
        def Dataset(x, y, params, categorical_feature):
            return None

        class Booster:
            train_record = Record()

            def __init__(self, params, train_set):
                seen.append(params)

            def update(self):
                pass

    tlc.require_cat_record(Lgb, params)
    assert seen[0]["tpu_histogram_impl"] == impl and seen[0]["categorical_feature"] == [1]
    assert "objective" in seen[0] and seen[0]["num_leaves"] == 4

@pytest.mark.parametrize("grower, cat_splits, says", [
    ({"row_update": "xla", "efb": False}, [3, 4], "not by its kernel"),
    ({"row_update": "kernel", "efb": True}, [3, 4], "bundled"),
    ({}, [3, 4], "not by its kernel"),
    ({"row_update": "kernel", "efb": False}, [0, 0], "no tree of the window"),
])
def test_a_path_the_cell_does_not_describe_ends_the_run(grower, cat_splits, says):
    from chipbench.drivers import train_loop_cat as tlc
    tlc.require_paths({"row_update": "kernel", "efb": False}, [0, 2])
    with pytest.raises(SystemExit, match=says):
        tlc.require_paths(grower, cat_splits)


def test_the_manifest_with_five_cells_passes(tmp_path):
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    assert len(m["configs"]) == 5 and len(m["workloads"]) == 5
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["criteo-q8-dp4.train"]
    cell = mf.find_named(m["workloads"], "criteo-cat-q8.train", "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("criteo-kaggle-cat-q8", "train-steady-blocks-cat", 1)
    mine = {x["name"] for x in mf.metrics_for(m, cell["name"], "per_layer")}
    q8 = {x["name"] for x in mf.metrics_for(m, "criteo-q8.train", "per_layer")}
    assert mine == q8 | set(helpers_cat.CAT_METRICS)
    for name in helpers_cat.CAT_METRICS:
        assert mf.find_named(m["per_layer"], name, "metric")["workloads"] == [cell["name"]]
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/criteo-kaggle-cat-q8.json")
    assert cfg["reduced"] == ["num_trees"] and cfg["data"]["rows"] == 45_840_617
    assert cfg["data"]["rows"] == cfg["upstream"]["rows"]
    assert cfg["params"]["categorical_feature"] == list(range(13, 39))
    assert set(cfg["limits"]) == {
        "leaf_count_diff", "leaf_value_gap", "split_gain_gap", "split_gain_median_gap",
        "train_score_gap", "heldout_pred_gap", "cat_law_violations", "cat_search_gap"}
    spec = datagen_ctr.CtrSpec(cfg["data"])
    assert spec.blocks == 175 and max(spec.ids(j) for j in range(26)) == 65536
    assert validate.validate(helpers_cat.make_root(str(tmp_path))) == []
