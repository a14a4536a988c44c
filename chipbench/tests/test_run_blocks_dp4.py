"""``chipbench.run`` on the four-device cell at a tiny size: the driver
``train_loop_blocks`` end to end through the device stub (four CPU devices
stand in), judged by the plain reference; and with one shard's histograms left
out of the merge, which has to read ``correct`` false."""

import json

import numpy as np
import pytest

from chipbench import roofline, run
from chipbench.tests import helpers, helpers_dp4

helpers_dp4.ask_for_devices()


@pytest.fixture(autouse=True)
def cpu_stands_in(monkeypatch):
    import jax
    if len(jax.devices()) < helpers_dp4.CHIPS:
        pytest.skip("JAX was up with fewer than four CPU devices")
    monkeypatch.setattr(run, "find_device", lambda chips: dict(helpers.CPU_DEVICE, count=4))
    real = roofline.load_peaks
    monkeypatch.setattr(roofline, "load_peaks", lambda kind, path=None: real("TPU v5 lite"))
    jax.config.update("jax_enable_compilation_cache", False)


def drive(tmp_path, capsys, trace=0, seed=2**31 + 7):
    root = helpers_dp4.make_root(str(tmp_path))
    rc = run.main(["--workload", "tiny-dp4.train", "--seed", str(seed), "--seconds", "0.5",
                   "--trace", str(trace)], root=root)
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


def test_a_sound_run_on_the_mesh_is_correct(tmp_path, capsys):
    line, err = drive(tmp_path, capsys)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    assert set(line["metrics"]) == {"train_iters_per_s", "heldout_auc_6", "setup_s"}
    assert line["metrics"]["heldout_auc_6"]["value"] > 0.7
    assert line["checks"]["leaf_count_diff"] == {"value": 0.0, "limit": 0}
    notes = line["notes"]
    assert notes["mesh"] == {"chips": 4, "axis": "workers", "rows_per_chip": 1500}
    assert "data_parallel/wave/hist_reduce_scatter" in notes["collectives"]
    assert len(notes["device_peak_bytes"]) >= 4 and notes["compiles_in_window"] == 0
    assert "6000+512 x 6 made" in err and "float32 blocks" in err


def test_a_traced_run_reports_the_mesh_metrics_it_can_read(tmp_path, capsys):
    line, _ = drive(tmp_path, capsys, trace=1)
    assert line["correct"] is True
    m = line["metrics"]
    # a number that repeats exactly: 14 channels x 8 padded features x 63 bins x 3 int32 sums
    assert m["collective_bytes_per_pass"] == {"value": 14 * 8 * 63 * 3 * 4.0, "unit": "bytes"}
    assert m["hist_passes_per_tree"]["value"] > 0 and m["bin_find_s"]["value"] > 0
    # the CPU trace has no device plane: the readers of device time say nothing
    for name in ("collective_device_ms_per_tree", "mesh_hist_kernel_roofline",
                 "hist_kernel_ms_per_pass"):
        assert name not in m


def test_a_dropped_shard_is_not_correct(tmp_path, capsys, monkeypatch):
    helpers_dp4.drop_one_shard(monkeypatch)
    line, err = drive(tmp_path, capsys)
    assert line["correct"] is False
    failed = {name for name, c in line["checks"].items() if not c["value"] <= c["limit"]}
    assert failed & {"leaf_count_diff", "split_gain_gap"}, failed
    assert err.strip().splitlines()[-1] == "correct False"


def test_a_program_without_block_input_ends_before_any_data_is_made(tmp_path, capsys, monkeypatch):
    """The parent of the PR that brought block input: ``np.asarray`` of a list
    of blocks is a 3-D array, and ``construct`` fails on it."""
    import lightgbm_tpu as lgb
    from chipbench import datagen

    def old_materialize(self):
        raw = np.asarray(self.data, dtype=np.float64)
        return raw, [f"Column_{i}" for i in range(raw.shape[1])]
    monkeypatch.setattr(lgb.Dataset, "_materialize_raw", old_materialize)
    monkeypatch.setattr(datagen, "map_blocks", lambda *a, **k: pytest.fail("data was made"))
    root = helpers_dp4.make_root(str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "tiny-dp4.train", "--seed", "1", "--seconds", "0.5",
                  "--trace", "0"], root=root)
    assert "takes no list of row blocks" in str(exc.value.code)
    assert capsys.readouterr().out.strip() == ""          # no result line


@pytest.mark.parametrize("built", [
    {"chips": 8, "axis": "workers", "rows_per_chip": 750},      # more visible devices
    {"chips": 1, "axis": None, "rows_per_chip": 6000},          # tree_learner ignored
    {}])                                                        # a program with no mesh record
def test_a_mesh_other_than_the_configurations_ends_the_run(built):
    """The mesh readers divide by the configuration's rows a chip: a program
    that sharded otherwise would read a multiple of the truth."""
    from chipbench.drivers import train_loop_blocks as tlb
    stated = {"chips": 4, "rows_per_chip": 1500}
    tlb.require_mesh({"chips": 4, "axis": "workers", "rows_per_chip": 1500}, stated)
    with pytest.raises(SystemExit, match="built the mesh"):
        tlb.require_mesh(built, stated)
