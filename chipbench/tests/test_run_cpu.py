"""``chipbench.run`` end to end on the CPU at a tiny size, the device check
stubbed on the test's side (the shipped command has no CPU fallback), and the
timed path broken underneath to see ``correct`` come out false."""

import json
import subprocess
import sys

import numpy as np
import pytest

from chipbench import roofline, run
from chipbench.tests import helpers


@pytest.fixture(autouse=True)
def cpu_stands_in(monkeypatch):
    monkeypatch.setattr(run, "find_device", lambda chips: dict(helpers.CPU_DEVICE))
    real = roofline.load_peaks
    monkeypatch.setattr(roofline, "load_peaks", lambda kind, path=None: real("TPU v5 lite"))
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def drive(tmp_path, capsys, quantized=True, trace=0, seed=2**31 + 5):
    root = helpers.make_root(str(tmp_path), quantized=quantized)
    rc = run.main(["--workload", "tiny.train", "--seed", str(seed), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("quantized", [True, False], ids=["q8", "exact"])
def test_a_sound_run_is_correct(tmp_path, capsys, quantized):
    line, err = drive(tmp_path, capsys, quantized)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    assert set(line["metrics"]) == {"train_iters_per_s", "heldout_auc_6", "setup_s"}
    assert line["metrics"]["heldout_auc_6"]["value"] > 0.7
    assert line["device"]["platform"] == "cpu"            # every line names its device
    assert list(line)[-1] == "checks"                     # the compared numbers come last
    assert set(line["checks"]) == {"leaf_count_diff", "leaf_value_gap", "split_gain_gap",
                                   "split_gain_median_gap", "train_score_gap",
                                   "heldout_pred_gap"}
    assert line["checks"]["leaf_count_diff"] == {"value": 0.0, "limit": 0}
    assert err.strip().splitlines()[-1] == "correct True"
    assert "check split_gain_gap value" in err
    assert line["notes"]["compiles_in_window"] == 0


def test_a_traced_run_reports_the_per_layer_metrics_it_can_read(tmp_path, capsys):
    line, _ = drive(tmp_path, capsys, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    # the CPU trace has no device plane: readers of kernel time find nothing and say nothing
    assert "hist_kernel_ms_per_pass" not in m and "hist_kernel_roofline" not in m
    assert m["hist_passes_per_tree"]["value"] > 0 and m["binning_s"]["value"] > 0
    assert m["trees_in_window"]["value"] == line["attempted"]     # the metric added by a file
    assert "busy_s" in line["device"] and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _break(monkeypatch, fault):
    import lightgbm_tpu as lgb
    if fault == "state_unchanged":
        # a step that returns its state unchanged: the tree is recorded, the scores are not moved
        real = lgb.Booster.update

        def update(self, *a, **k):
            before = self._gbdt.score
            out = real(self, *a, **k)
            self._gbdt.score = before
            return out
        monkeypatch.setattr(lgb.Booster, "update", update)
    elif fault == "half_batch":
        # half of the batch left out, the sums taken over the rest
        real = lgb.Dataset

        def dataset(X, y, **k):
            return real(X[: len(X) // 2], y[: len(y) // 2], **k)
        monkeypatch.setattr(lgb, "Dataset", dataset)
    elif fault == "answer_altered":
        # an answer altered where it is produced: one leaf of the second tree, by 2%
        real = lgb.Booster.model_to_string

        def text(self, *a, **k):
            lines = real(self, *a, **k).splitlines()
            at = [i for i, ln in enumerate(lines) if ln.startswith("leaf_value=")][1]
            vals = lines[at].split("=")[1].split()
            vals[3] = repr(float(vals[3]) * 1.02)
            lines[at] = "leaf_value=" + " ".join(vals)
            return "\n".join(lines)
        monkeypatch.setattr(lgb.Booster, "model_to_string", text)
    elif fault == "prediction_altered":
        real = lgb.Booster.predict

        def predict(self, data, **k):
            p = np.array(real(self, data, **k))
            p[0] += 1e-3
            return p
        monkeypatch.setattr(lgb.Booster, "predict", predict)


@pytest.mark.parametrize("fault, caught_by", [
    ("state_unchanged", "leaf_value_gap"),
    ("half_batch", "leaf_count_diff"),
    ("answer_altered", "leaf_value_gap"),
    ("prediction_altered", "heldout_pred_gap"),
])
def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch, fault, caught_by):
    _break(monkeypatch, fault)
    line, err = drive(tmp_path, capsys)
    assert line["correct"] is False
    c = line["checks"][caught_by]
    assert not c["value"] <= c["limit"]
    assert err.strip().splitlines()[-1] == "correct False"
    assert f"check {caught_by}" in err and "FAILED" in err


def test_off_the_chip_the_shipped_command_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "criteo-q8.train", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=helpers.REPO, capture_output=True, text=True, timeout=300,
        env={k: v for k, v in __import__("os").environ.items() if k != "JAX_PLATFORMS"}
        | {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "Not running" in proc.stderr
