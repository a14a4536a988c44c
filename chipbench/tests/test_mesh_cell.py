"""The four-chip cell's entries and its four readers, on hand-made facts: the
manifest with the new entries validates, the ``mesh_*`` readers set ONE chip's
rows against one chip's peaks (a quarter of what the one-chip readers read on
the same numbers), the collective readers read the program's scopes and tally."""

import json
import os

import pytest

from chipbench import manifest as mf
from chipbench import roofline, validate
from chipbench.facts import Facts
from chipbench.tests import helpers, helpers_dp4

MS = 1_000_000
CELL = "criteo-q8-dp4.train"


def reader(name):
    return mf.load_module(mf.metric_file(helpers.REPO, mf.load_manifest(helpers.REPO), name))


def test_the_manifest_has_the_cell_and_validates(tmp_path):
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    cell = mf.find_named(m["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("criteo-share-q8-dp4", "train-steady-blocks", 4)
    cfg = mf.load_json(os.path.join(helpers.REPO, "chipbench/configs/criteo-share-q8-dp4.json"))
    one = mf.load_json(os.path.join(helpers.REPO, "chipbench/configs/criteo-share-q8.json"))
    assert cfg["params"] == dict(one["params"], tree_learner="data")
    assert cfg["limits"].keys() == one["limits"].keys() and cfg["control"] == one["control"]
    # no limit wider than the one-chip cell's, and no placeholder left for a reading
    assert all(cfg["limits"][k] <= one["limits"][k] for k in cfg["limits"])
    assert "TO_FILL" not in json.dumps(cfg)
    assert cfg["data"]["rows"] == cfg["mesh"]["chips"] * cfg["mesh"]["rows_per_chip"]
    assert cfg["mesh"]["rows_per_chip"] == one["data"]["rows"]     # the one-chip cells' rows a chip
    assert {k: v for k, v in cfg["data"].items() if k != "rows"} == \
        {k: v for k, v in one["data"].items() if k != "rows"}
    mix = mf.load_json(mf.mix_file(helpers.REPO, m, cell["traffic"]))
    steady = mf.load_json(mf.mix_file(helpers.REPO, m, "train-steady"))
    assert mix["driver"] == "train_loop_blocks"
    numbers = lambda d: {k: v for k, v in d.items() if isinstance(v, int)}
    assert numbers(mix) == numbers(steady)
    names = [x["name"] for x in mf.metrics_for(m, CELL, "per_layer")]
    assert set(helpers_dp4.MESH_METRICS) <= set(names)
    # total rows against one chip's peaks would read four times too high
    assert "hist_kernel_roofline" not in names and "tree_step_mfu" not in names
    for name in helpers_dp4.MESH_METRICS:
        entry = mf.find_named(m["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL] and entry["layer"] == "distribution"
    # the tests' own four-device root validates too
    assert validate.validate(helpers_dp4.make_root(str(tmp_path))) == []


class Trace:
    window = (0, 12_000 * MS)
    window_s, busy_s = 12.0, 11.9

    def matching_s(self, needle):
        return 6.6 if needle == "lgbm_hist_" else 0.0


def facts(mesh):
    cfg = mf.load_json(os.path.join(helpers.REPO, "chipbench/configs/criteo-share-q8.json"))
    if mesh:
        cfg = dict(cfg, data=dict(cfg["data"], rows=4 * cfg["data"]["rows"]),
                   mesh={"chips": 4, "rows_per_chip": cfg["data"]["rows"]})
    counters = {"traced_trees": 3, "hist_passes": [7, 7, 7, 8]}
    return Facts(cfg, {"kind": "TPU v5 lite"}, roofline.load_peaks("TPU v5 lite"), counters, Trace())


@pytest.mark.parametrize("mesh_name, one_chip_name", [
    ("mesh_hist_kernel_roofline", "hist_kernel_roofline"),
    ("mesh_tree_step_mfu", "tree_step_mfu")])
def test_mesh_readers_set_one_chips_rows_against_one_chips_peaks(mesh_name, one_chip_name):
    on_mesh = reader(mesh_name).read(facts(mesh=True))
    # the one-chip reader on the one-chip cell with the same kernel time: the same share
    assert on_mesh == pytest.approx(reader(one_chip_name).read(facts(mesh=False)), rel=1e-12)
    # ... and on the mesh's total rows it reads four times that: why the cell does not list it
    assert reader(one_chip_name).read(facts(mesh=True)) == pytest.approx(4 * on_mesh, rel=1e-12)
    assert 0 < on_mesh < 100
    # a configuration without a mesh group, or an untraced run: nothing to read
    assert reader(mesh_name).read(facts(mesh=False)) is None
    untraced = facts(mesh=True)
    untraced.trace = None
    assert reader(mesh_name).read(untraced) is None


def test_collective_bytes_reads_the_largest_histogram_operand():
    read = reader("collective_bytes_per_pass").read
    f = facts(mesh=True)
    assert read(f) is None                                    # the parent: no such counter
    f.counters["collectives"] = {
        "data_parallel/wave/hist_reduce_scatter": {"op": "psum_scatter", "count": 9, "bytes": 19663560,
                                                   "operand_bytes": 78654240,
                                                   "max_operand_bytes": 8739360},
        "data_parallel/wave/winner_exchange": {"op": "pmax", "count": 30, "bytes": 16840,
                                               "operand_bytes": 16840, "max_operand_bytes": 2688}}
    assert read(f) == 42 * 68 * 255 * 3 * 4 == 8739360
    f.counters["collectives"] = {"data_parallel/wave/winner_exchange": {"max_operand_bytes": 2688}}
    assert read(f) is None                                    # no histogram site traced


DP_EVENTS = [
    # device 0: a merge of 4 ms inside its phase, the winner exchange, a scalar; and other work
    (0, "%all-reduce.1 = s32[42,68,255,3] all-reduce(s32[42,68,255,3] %h)", "lgbm.dp.hist_reduce", 0, 4 * MS),
    (0, "%fusion.2 = f32[84,17,255,3] fusion(s32[42,17,255,3] %x)", "lgbm.wave.scan", 4 * MS, 9 * MS),
    (0, "%all-reduce.3 = f32[84] all-reduce(f32[84] %g)", "lgbm.dp.exchange", 9 * MS, 10 * MS),
    (0, "%all-reduce.4 = f32[2] all-reduce(f32[2] %m)", "lgbm.dp.scalar", 10 * MS, 10 * MS + 500_000),
    # overlapping events of one scope count once; a loop counts for nothing itself
    (0, "%all-reduce.5 = f32[84] all-reduce(f32[84] %g)", "lgbm.dp.exchange", 9 * MS + 500_000, 10 * MS),
    (0, "%while.6 = (s32[]) while((s32[]) %t), body=%b", "lgbm.dp.exchange", 0, 20 * MS),
    # device 1 waited for device 0: its merge took 8 ms
    (1, "%all-reduce.1 = s32[42,68,255,3] all-reduce(s32[42,68,255,3] %h)", "lgbm.dp.hist_reduce", 0, 8 * MS),
    (1, "%fusion.9 = f32[8] fusion(f32[8] %x)", None, 8 * MS, 9 * MS),
]


def test_collective_device_time_is_the_union_of_the_dp_scopes_a_device(tmp_path, monkeypatch):
    from chipbench import scope_reduce as sr
    mod = reader("collective_device_ms_per_tree")
    assert mod.collective_ns(DP_EVENTS) == ((4 + 1 + 0.5) * MS + 8 * MS) / 2
    assert mod.collective_ns([e for e in DP_EVENTS if e[2] == "lgbm.wave.scan"]) == 0
    # the run's trace, found as scope_reduce finds it, clipped to the run's window
    xplane = tmp_path / "h.xplane.pb"
    xplane.write_bytes(b"")
    monkeypatch.setattr(sr, "newest_xplane", lambda root: str(xplane))
    monkeypatch.setattr(sr, "read_scoped_events", lambda xspace: (DP_EVENTS, (0, 12_000 * MS)))
    f = facts(mesh=True)
    assert mod.read(f) == pytest.approx((5.5 + 8) / 2 / 3)      # ms a traced tree
    # a program without the scopes (the parent), another run's trace, no trace at all: nothing
    bare = [(d, n, None if sc and sc.startswith("lgbm.dp.") else sc, s, e)
            for d, n, sc, s, e in DP_EVENTS]
    monkeypatch.setattr(sr, "read_scoped_events", lambda xspace: (bare, (0, 12_000 * MS)))
    assert mod.read(f) is None
    monkeypatch.setattr(sr, "read_scoped_events", lambda xspace: (DP_EVENTS, (5, 12_000 * MS)))
    assert mod.read(f) is None
    monkeypatch.setattr(sr, "newest_xplane", lambda root: None)
    assert mod.read(f) is None
    f.trace = None
    assert mod.read(f) is None
