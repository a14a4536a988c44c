"""``chipbench.run`` on the ``boosting=goss`` cell at a tiny size: the driver
``train_loop_goss`` end to end through the device stub, judged by
``chipbench.reference_goss``; the control and the four planted faults, each
of which has to read ``correct`` false by the number that is there to catch
it; a program without ``last_sample()``; a window tree that was not sampled."""

import functools
import json

import pytest

from chipbench import datagen, reference_goss, roofline, run, validate
from chipbench import manifest as mf
from chipbench.tests import helpers, helpers_goss

SEED = 2**31 + 9


@pytest.fixture(autouse=True)
def cpu_stands_in(monkeypatch):
    monkeypatch.setattr(run, "find_device", lambda chips: dict(helpers.CPU_DEVICE))
    real = roofline.load_peaks
    monkeypatch.setattr(roofline, "load_peaks", lambda kind, path=None: real("TPU v5 lite"))
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def drive(tmp_path, capsys, trace=0, control=False):
    root = helpers_goss.make_root(str(tmp_path), control=control)
    rc = run.main(["--workload", "tiny-goss.train", "--seed", str(SEED), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def failed(line) -> set:
    return {name for name, c in line["checks"].items() if not c["value"] <= c["limit"]}


def test_a_sound_goss_run_is_correct(tmp_path, capsys):
    line, err = drive(tmp_path, capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    assert set(line["metrics"]) == {"train_iters_per_s", "heldout_auc_6", "setup_s"}
    assert set(line["checks"]) == {
        "goss_top_violations", "goss_top_share_gap", "goss_rest_rate_gap", "goss_rest_bias",
        "leaf_count_diff", "leaf_value_gap", "split_gain_gap", "split_gain_median_gap",
        "train_score_gap", "heldout_pred_gap"}
    assert line["checks"]["goss_top_violations"] == {"value": 0.0, "limit": 0}
    assert line["checks"]["leaf_count_diff"] == {"value": 0.0, "limit": 0}
    notes = line["notes"]
    assert notes["compiles_in_window"] == 0            # the sampler compiled in the warm-up
    assert len(notes["sampled_rows"]) == line["attempted"]
    assert all(abs(n / helpers_goss.ROWS - 0.3) < 0.01 for n in notes["sampled_rows"])
    assert "2+3 warm-up trees, 3 draws copied" in err
    assert err.strip().splitlines()[-1] == "correct True"


def test_a_traced_goss_run_reports_the_metrics_it_can_read(tmp_path, capsys):
    line, _ = drive(tmp_path, capsys, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    assert abs(m["sampled_row_share"]["value"] - 0.3) < 0.01
    assert m["sampled_row_share"]["unit"] == "share"
    assert m["hist_passes_per_tree"]["value"] > 0 and m["bin_find_s"]["value"] > 0
    # the CPU trace has no device plane: the readers of device time say nothing
    for name in ("goss_sample_device_ms_per_tree", "goss_hist_kernel_roofline",
                 "hist_kernel_ms_per_pass"):
        assert name not in m
    assert "hist_kernel_roofline" not in m and "tree_step_mfu" not in m


def _plant(monkeypatch, fault):
    real = reference_goss.follow_sampled
    rp = reference_goss.Params({"objective": "binary", "boosting": "goss", "learning_rate": 0.5,
                                "top_rate": 0.2, "other_rate": 0.1})
    how = {
        "amplification_1": dict(amplification=1.0),
        "rest_at_other_rate": dict(alter=reference_goss.redraw_rest(rp.other_rate, SEED)),
        "subsample_threshold": dict(alter=reference_goss.subsample_threshold(rp)),
        "draw_favours_large": dict(alter=reference_goss.favour_large(rp.rest_rate, SEED)),
    }[fault]
    monkeypatch.setattr(reference_goss, "follow_sampled", functools.partial(real, **how))


@pytest.mark.parametrize("fault, caught_by", [
    ("amplification_1", "leaf_value_gap"),
    ("rest_at_other_rate", "goss_rest_rate_gap"),
    ("subsample_threshold", "goss_top_violations"),
    ("draw_favours_large", "goss_rest_bias"),
    ("int4_for_int8", "split_gain_median_gap"),
])
def test_a_planted_fault_is_not_correct(tmp_path, capsys, monkeypatch, fault, caught_by):
    if fault != "int4_for_int8":
        _plant(monkeypatch, fault)
    line, err = drive(tmp_path, capsys, control=fault == "int4_for_int8")
    assert line["correct"] is False
    assert caught_by in failed(line), line["checks"]
    assert err.strip().splitlines()[-1] == "correct False"
    assert f"check {caught_by}" in err and "FAILED" in err


def test_a_program_without_last_sample_ends_before_any_data_is_made(tmp_path, capsys, monkeypatch):
    """The parent of the PR that brought the draw to the device."""
    from lightgbm_tpu.models.gbdt import GBDT
    monkeypatch.delattr(GBDT, "last_sample")
    monkeypatch.setattr(datagen, "training_matrix", lambda *a, **k: pytest.fail("data was made"))
    root = helpers_goss.make_root(str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "tiny-goss.train", "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=root)
    assert "no last_sample()" in str(exc.value.code)
    assert capsys.readouterr().out.strip() == ""          # no result line


@pytest.mark.parametrize("bags", [
    [6000, 1800, 1800],          # a tree of the warm-up's kind in the window
    [1800, 1800, 1300],          # a sampler that keeps another share
    [1800, 0]])                  # a program that counts no bag
def test_a_window_tree_that_was_not_sampled_ends_the_run(bags):
    from chipbench.drivers import train_loop_goss as tlg
    tlg.require_sampled([1800, 1790, 1845], 6000, 0.3)
    with pytest.raises(SystemExit, match="Not a goss window"):
        tlg.require_sampled(bags, 6000, 0.3)


def test_warm_up_trees_that_do_not_match_the_mix_end_the_run(tmp_path, capsys, monkeypatch):
    """A sampler that starts a tree early (or late) would put an unfollowed
    kind of tree where the reference expects the other."""
    from lightgbm_tpu.models import boosting
    real = boosting.goss_rates
    monkeypatch.setattr(boosting, "goss_rates", lambda cfg, it: real(cfg, it + 1))
    root = helpers_goss.make_root(str(tmp_path))
    with pytest.raises(SystemExit, match="the mix states 2 unsampled trees"):
        run.main(["--workload", "tiny-goss.train", "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=root)


def test_the_manifest_with_the_goss_cell_passes(tmp_path):
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    assert len(m["configs"]) == 4 and len(m["workloads"]) == 4
    assert [w["name"] for w in m["workloads"] if w["chips"] == 4] == ["criteo-q8-dp4.train"]
    cell = mf.find_named(m["workloads"], "criteo-q8-goss.train", "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("criteo-share-q8-goss", "train-steady-goss", 1)
    mine = {x["name"] for x in mf.metrics_for(m, cell["name"], "per_layer")}
    assert set(helpers_goss.GOSS_METRICS) <= mine
    # a floor over all N rows is not a sampled tree's floor, and one chip has no mesh
    assert not mine & {"hist_kernel_roofline", "tree_step_mfu", "mesh_tree_step_mfu",
                       "collective_bytes_per_pass"}
    for name in helpers_goss.GOSS_METRICS:
        assert mf.find_named(m["per_layer"], name, "metric")["workloads"] == [cell["name"]]
    cfg = mf.load_json(f"{helpers.REPO}/chipbench/configs/criteo-share-q8-goss.json")
    q8 = mf.load_json(f"{helpers.REPO}/chipbench/configs/criteo-share-q8.json")
    assert cfg["params"] == dict(q8["params"], boosting="goss", top_rate=0.2, other_rate=0.1)
    assert cfg["data"] == q8["data"] and cfg["reduced"] == q8["reduced"]
    assert set(cfg["limits"]) == set(q8["limits"]) | {
        "goss_top_violations", "goss_top_share_gap", "goss_rest_rate_gap", "goss_rest_bias"}
    assert validate.validate(helpers_goss.make_root(str(tmp_path))) == []
