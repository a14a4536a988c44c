"""The benchmark's sparse one-hot cell at a tiny size, in the tier-1 suite:
q8, Exclusive Feature Bundling on, rows routed by the row-update kernel's
bundled slots, the driver ``train_loop_sparse`` end to end through the device
stub, judged ``correct`` by ``chipbench/reference_sparse.py`` (raw columns
walked in float64 with no notion of a bundle, every indicator column searched
again); the control and the four planted faults read ``correct`` false, each by
the number that is there to catch it; a program whose record states no ``efb``
ends before any data is made.  With them the manifest with six cells, the
generator, and the two roofline readers that count a row's source columns."""

import functools
import json
import os

import numpy as np
import pytest

from chipbench import datagen_sparse, reference_sparse, roofline, run, validate
from chipbench import manifest as mf
from chipbench.facts import Facts
from chipbench.tests import helpers

SEED = 2**31 + 11
CELL = "allstate-efb-q8.train"
EFB_METRICS = ("efb_expand_device_ms_per_tree", "efb_bundle_s", "efb_column_share",
               "efb_hist_kernel_roofline", "efb_tree_step_mfu")
TINY_DATA = {
    "generator": "allstate_onehot_like", "rows": 6000, "features": 165, "source_columns": 9,
    "holdout_rows": 512, "weights_seed": 5, "zipf_exponent": 1.0, "claim_rate": 0.3,
    "signal": 3.0,
    "columns": [["Age", 0], ["Make", 6], ["Model", 20], ["Sub", 120], ["V1", 0], ["C1", 3],
                ["C2", 12], ["V2", 0], ["NV", 0]],
    "numeric": {"Age": ["age", 2008, 7.0, 27], "V1": ["normal"], "V2": ["normal"],
                "NV": ["zero_exp", 0.8]},
    "nested": {"Model": "Sub", "Make": "Model"},
}
# at 6000 rows int8 levels read 6e-4 at the median node where int4 reads 1e-2; no
# indicator column offers more than the committed split (1e-15), a scan that skips
# every second bundle leaves 0.15 unseen
LOOSE = {"conflict_statement_errors": 0, "indicator_search_gap": 0.05,
         "split_gain_median_gap": 4e-3}
TINY_MIX = dict(helpers.TINY_MIX, name="tiny-steady-sparse", driver="train_loop_sparse")


def tiny_config(control: bool = False) -> dict:
    cfg = helpers.tiny_config("tiny-sparse", True)
    cfg["data"] = dict(TINY_DATA)
    cfg["params"].update(min_data_in_leaf=0, min_sum_hessian_in_leaf=8.0, enable_bundle=True,
                         min_data_in_bin=1, max_bin=255)
    if control:
        cfg["params"].update(cfg["control"]["params"])
    cfg["limits"].update(LOOSE)
    return cfg


def make_root(tmp: str, control: bool = False) -> str:
    root = helpers.make_root(tmp, quantized=True)
    extra = os.path.join(root, "extrabench")
    with open(os.path.join(extra, "configs", "tiny-sparse.json"), "w") as fh:
        json.dump(tiny_config(control), fh)
    with open(os.path.join(extra, "workloads", "tiny-steady-sparse.json"), "w") as fh:
        json.dump(TINY_MIX, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({"name": "tiny-sparse", "source": "test", "reduced": [],
                                "why": "test", "file": "extrabench/configs/tiny-sparse.json"})
    manifest["workloads"].append({"name": "tiny-sparse.train", "config": "tiny-sparse",
                                  "traffic": "tiny-steady-sparse", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"].append("tiny-sparse.train")
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


def drive(tmp_path, capsys, trace=0, control=False):
    root = make_root(str(tmp_path), control=control)
    rc = run.main(["--workload", "tiny-sparse.train", "--seed", str(SEED), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.fixture
def conflicts_allowed(monkeypatch):
    """FindGroups' rule allows a bundle 1e-4 of the binning sample in conflicts:
    none at 6000 rows.  At 1e-2 levels of one coded column join another's
    bundle, as they do at the cell's 12M rows."""
    from lightgbm_tpu import efb
    monkeypatch.setattr(efb, "find_bundles",
                        functools.partial(efb.find_bundles, conflict_rate=1e-2))


def failed(line) -> set:
    return {name for name, c in line["checks"].items() if not c["value"] <= c["limit"]}


def test_the_manifest_with_six_cells_passes(tmp_path):
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    assert len(m["configs"]) >= 6 and len(m["workloads"]) >= 6
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["criteo-q8-dp4.train"]
    cell = mf.find_named(m["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("allstate-onehot-efb-q8", "train-steady-sparse", 1)
    mine = {x["name"] for x in mf.metrics_for(m, CELL, "per_layer")}
    assert set(EFB_METRICS) <= mine and "row_update_kernel_roofline" in mine
    # a floor over 4,228 one-byte columns a row would read far over 100%
    assert not {"hist_kernel_roofline", "tree_step_mfu"} & mine
    for name in EFB_METRICS:
        assert mf.find_named(m["per_layer"], name, "metric")["workloads"] == [CELL]
        assert os.path.isfile(mf.metric_file(helpers.REPO, m, name))
    assert os.path.isfile(mf.mix_file(helpers.REPO, m, cell["traffic"]))
    mix = mf.load_json(mf.mix_file(helpers.REPO, m, cell["traffic"]))
    assert os.path.isfile(mf.driver_file(helpers.REPO, m, mix["driver"]))
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/allstate-onehot-efb-q8.json")
    assert cfg["reduced"] == ["num_trees"] and cfg["data"]["rows"] == 12_184_290
    assert cfg["data"]["rows"] + cfg["data"]["holdout_rows"] == cfg["upstream"]["rows"]
    p = cfg["params"]
    assert (p["num_leaves"], p["learning_rate"], p["min_data_in_leaf"],
            p["min_sum_hessian_in_leaf"], p["enable_bundle"]) == (255, 0.1, 0, 100, True)
    assert set(cfg["limits"]) == {
        "leaf_count_diff", "leaf_value_gap", "split_gain_gap", "split_gain_median_gap",
        "train_score_gap", "heldout_pred_gap", "conflict_statement_errors",
        "indicator_search_gap"}
    spec = datagen_sparse.SparseSpec(cfg["data"])
    assert spec.features == 4228 and spec.is_indicator.sum() == 4213 and spec.blocks == 47
    assert (spec.n_num, spec.n_coded) == (15, 17)
    assert validate.validate(make_root(str(tmp_path))) == []


def test_a_block_made_alone_is_the_block_made_in_sequence():
    spec = datagen_sparse.SparseSpec(dict(TINY_DATA, rows=2 * datagen_sparse.BLOCK_ROWS // 64))
    spec_small = datagen_sparse.SparseSpec(TINY_DATA)
    t = datagen_sparse.Tables(spec_small)
    x, y = datagen_sparse.training_matrix(spec_small, SEED, t)
    xb, yb = datagen_sparse.block(spec_small, SEED, 0)       # its own tables: the same
    assert (x != xb).nnz == 0 and np.array_equal(y, yb)
    assert x.dtype == np.float32 and x.indices.dtype == np.int32 and x.has_sorted_indices
    other, _ = datagen_sparse.block(spec_small, SEED + 1, 0, t)
    assert (x != other).nnz > 0
    assert spec.blocks == 1
    # exactly one indicator of every coded column is set in every row
    dense = x.toarray()
    for name in spec_small.coded_names:
        o, k = spec_small.offset[name], spec_small.levels[name]
        assert np.array_equal(dense[:, o:o + k].sum(axis=1), np.ones(len(dense)))
        assert set(np.unique(dense[:, o:o + k])) == {0.0, 1.0}
    # a submodel has one model, a model one make
    sub = dense[:, spec_small.offset["Sub"]:][:, :120].argmax(axis=1)
    model = dense[:, spec_small.offset["Model"]:][:, :20].argmax(axis=1)
    assert len({(s, m) for s, m in zip(sub, model)}) == len(set(sub))
    xh, yh = datagen_sparse.holdout(spec_small, SEED, t)
    assert xh.shape == (512, 165) and abs(yh.mean() - 0.3) < 0.1
    assert abs(y.mean() - 0.3) < 0.05


def test_a_sound_sparse_run_is_correct(tmp_path, capsys):
    line, err = drive(tmp_path, capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4, \
        line["checks"]
    assert set(line["metrics"]) == {"train_iters_per_s", "heldout_auc_6", "setup_s"}
    assert set(line["checks"]) == {
        "leaf_count_diff", "leaf_value_gap", "split_gain_gap", "split_gain_median_gap",
        "train_score_gap", "heldout_pred_gap", "conflict_statement_errors",
        "indicator_search_gap"}
    assert line["checks"]["leaf_count_diff"] == {"value": 0.0, "limit": 0}
    assert line["checks"]["conflict_statement_errors"] == {"value": 0.0, "limit": 0}
    notes = line["notes"]
    assert notes["compiles_in_window"] == 0
    assert notes["grower"]["row_update"] == "kernel" and notes["grower"]["efb"] is True
    assert not (notes["grower"]["ramp"] or notes["grower"]["endgame"])
    assert notes["efb"]["features"] == 165 and notes["efb"]["bundles"] * 10 < 165
    assert min(notes["indicator_splits"]) > 0
    assert line["metrics"]["heldout_auc_6"]["value"] > 0.7
    assert err.strip().splitlines()[-1] == "correct True"


def test_a_sound_run_with_conflicts_is_correct(tmp_path, capsys, conflicts_allowed):
    """Rows in which a bundle's conflict overwrote a value: the program states
    them, the reference zeroes the stated entries and holds every row."""
    line, _ = drive(tmp_path, capsys)
    assert line["notes"]["efb"]["conflict_rows"] > 0
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["leaf_count_diff"]["value"] == 0
    assert line["checks"]["conflict_statement_errors"]["value"] == 0


def test_a_traced_sparse_run_reports_the_metrics_it_can_read(tmp_path, capsys):
    line, _ = drive(tmp_path, capsys, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["efb_column_share"]["value"] == pytest.approx(
        line["notes"]["efb"]["bundles"] / 165)
    assert m["efb_bundle_s"]["value"] > 0 and m["bin_matrix_s"]["value"] > 0
    assert m["bin_find_s"]["value"] > 0 and m["hist_passes_per_tree"]["value"] > 0
    assert m["endgame_passes_per_tree"]["value"] == 0 and m["ramp_committed_per_tree"]["value"] == 0
    # the CPU trace has no device plane: the readers of device time say nothing
    for name in ("efb_expand_device_ms_per_tree", "efb_hist_kernel_roofline",
                 "row_update_kernel_roofline", "hist_kernel_ms_per_pass"):
        assert name not in m


@pytest.mark.parametrize("fault, caught_by", [
    ("default_not_restored", {"split_gain_gap"}),
    ("offsets_swapped", {"leaf_count_diff"}),
    ("conflicts_by_loser", {"leaf_count_diff"}),
    ("skip_second_bundle", {"indicator_search_gap"}),
    ("int4_for_int8", {"split_gain_median_gap"}),
])
def test_a_planted_fault_is_not_correct(tmp_path, capsys, monkeypatch, conflicts_allowed,
                                        fault, caught_by):
    if fault != "int4_for_int8":
        monkeypatch.setattr(reference_sparse, "compare_run",
                            functools.partial(reference_sparse.compare_run, fault=fault))
    line, err = drive(tmp_path, capsys, control=fault == "int4_for_int8")
    assert line["correct"] is False
    assert caught_by & failed(line), line["checks"]
    assert err.strip().splitlines()[-1] == "correct False"


@pytest.mark.parametrize("efb, says", [(None, "no 'efb'"),
                                       ({"features": 11, "bundles": 11}, "no 'efb'")])
def test_a_program_without_the_record_ends_before_any_data_is_made(tmp_path, capsys,
                                                                   monkeypatch, efb, says):
    """The parent of the PR that put bundles on the normal path: its record
    states no ``efb``, and it would route every row through the XLA form."""
    from lightgbm_tpu.telemetry.train_record import TrainRecord
    real = TrainRecord.snapshot

    def snapshot(self):
        snap = real(self)
        snap.pop("efb")
        if efb is not None:
            snap["efb"] = efb
        return snap

    monkeypatch.setattr(TrainRecord, "snapshot", snapshot)
    monkeypatch.setattr(datagen_sparse, "training_matrix",
                        lambda *a, **k: pytest.fail("data made"))
    root = make_root(str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "tiny-sparse.train", "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=root)
    assert says in str(exc.value.code) and exc.value.code != 0
    assert capsys.readouterr().out.strip() == ""          # no result line


def test_the_probe_names_the_implementation_and_the_pipeline():
    from chipbench.drivers import train_loop_sparse as tls
    seen = []

    class Record:
        def snapshot(self):
            return {"efb": {"features": 11, "bundles": 4}}

    class Lgb:
        @staticmethod
        def Dataset(x, y, params):
            assert x.shape == (64, 11) and hasattr(x, "tocsr")
            return None

        class Booster:
            train_record = Record()

            def __init__(self, params, train_set):
                seen.append(params)

            def update(self):
                pass

    tls.require_efb_record(Lgb, {"objective": "binary", "tpu_wave_size": 8})
    assert seen[0]["tpu_histogram_impl"] == "pallas" and seen[0]["tpu_pallas_pipeline"] == "dma"
    assert seen[0]["enable_bundle"] is True and seen[0]["tpu_wave_size"] == 8


@pytest.mark.parametrize("record, splits, says", [
    ({"efb": {"features": 100, "bundles": 20}, "grower": {"efb": True, "row_update": "kernel"}},
     [3], "not bundled to under a tenth"),
    ({"grower": {"efb": True, "row_update": "kernel"}}, [3], "not bundled"),
    ({"efb": {"features": 100, "bundles": 5}, "grower": {"efb": False, "row_update": "kernel"}},
     [3], "states no efb"),
    ({"efb": {"features": 100, "bundles": 5}, "grower": {"efb": True, "row_update": "xla"}},
     [3], "not by its kernel"),
    ({"efb": {"features": 100, "bundles": 5}, "grower": {"efb": True, "row_update": "kernel"}},
     [0, 0], "no tree of the window"),
])
def test_a_path_the_cell_does_not_describe_ends_the_run(record, splits, says):
    from chipbench.drivers import train_loop_sparse as tls
    tls.require_paths({"efb": {"features": 100, "bundles": 5},
                       "grower": {"efb": True, "row_update": "kernel"}}, [0, 2])
    with pytest.raises(SystemExit, match=says):
        tls.require_paths(record, splits)


def test_the_two_rooflines_count_source_columns_and_read_under_100():
    """At the measured cat cell's kernel rate (7.78 GB streamed in 12.9 ms a
    64-column pass is past the peak; a pass of this cell's some 50 columns
    takes no less than its bytes at the peak) the share stays under 100, where
    a floor over the 4,228 coded columns would read thousands."""
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/allstate-onehot-efb-q8.json")
    peaks = roofline.load_peaks("TPU v5 lite")
    m = mf.load_manifest(helpers.REPO)
    hist = mf.load_module(mf.metric_file(helpers.REPO, m, "efb_hist_kernel_roofline"))
    mfu = mf.load_module(mf.metric_file(helpers.REPO, m, "efb_tree_step_mfu"))
    d = cfg["data"]
    # a kernel that streams 64 padded one-byte columns a row at the HBM peak
    pass_s = d["rows"] * 64 / peaks["hbm_bytes_per_s"]

    class Trace:
        window_s = 3 * 14 * pass_s * 1.5
        events = []

        def matching_s(self, needle):
            return 3 * 14 * pass_s

    facts = Facts(cfg, helpers.CPU_DEVICE, peaks,
                  {"traced_trees": 3, "hist_passes": [14, 14, 14]}, Trace())
    share = hist.read(facts)
    assert 40 < share < 100
    assert share == pytest.approx(100 * (d["rows"] * 32 + d["rows"] * 3) / (d["rows"] * 64))
    assert 0 < mfu.read(facts) < share / 14 * 1.01
    over = roofline.pass_floor(d["rows"], d["features"], 255, 255, "int8", peaks)["seconds"]
    assert over / pass_s > 50
    assert hist.read(Facts(dict(cfg, data={k: v for k, v in d.items() if k != "source_columns"}),
                           helpers.CPU_DEVICE, peaks, facts.counters, Trace())) is None
