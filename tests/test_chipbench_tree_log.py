"""The nine per-layer metrics that read the program's own log of its trees
(ISSUE 36; ``chipbench/layer_metrics/tree_log.py`` and the readers beside it):
each on hand-made snapshots, with the traced-tree skip and the cases in which
a reader finds nothing and says nothing; their entries in ``BENCHMARK.json``;
and a traced run of a tiny cell on the CPU, which has to print them (counts
and host spans: no device metric is read here)."""

import json

import pytest

from chipbench import manifest as mf, roofline, run, validate
from chipbench.facts import Facts
from chipbench.tests import helpers

CLOCK = ["tree_ms_p50", "tree_ms_max", "marginal_pass_ms",
         "host_dispatch_ms_per_tree", "host_wait_share"]
COUNTS = ["wave_rows_share", "endgame_rows_share",
          "compact_blocks_active_share", "ramp_sample_row_share"]
NUMERIC = ["criteo-q8.train", "criteo-exact.train", "criteo-q8-dp4.train",
           "criteo-q8-goss.train"]
CELLS = NUMERIC + ["criteo-cat-q8.train"]
NOT_IN_CAT = {"marginal_pass_ms", "endgame_rows_share", "ramp_sample_row_share"}


def read(name, facts):
    path = f"{helpers.REPO}/chipbench/layer_metrics/{name}.py"
    return mf.load_module(path).read(facts)


def _pass(kind, rows, active, blocks=10, held=10, leaves=4):
    return {"kind": kind, "leaves": leaves, "rows": rows, "active_rows": active,
            "blocks": blocks, "blocks_active": held}


def _tree(i, done, passes, wait=0.9, dispatch=0.05, ramp=(400, 400)):
    return {"iteration": i, "class_id": 0, "hist_passes": len(passes),
            "done_s": done, "wait_s": wait, "dispatch_s": dispatch,
            "passes": passes, "ramp_sample_rows": ramp[0],
            "ramp_sample_lanes": ramp[1]}


SEVEN = [_pass(0, 1000, 1000)] + [_pass(1, 400, 350, held=9)] * 2 + \
    [_pass(2, 200, 100, held=5)] * 4
NINE = SEVEN + [_pass(2, 100, 60, held=4)] * 2
# two warm-up trees, then a window of six; the newest has no stamp yet
TREES = [_tree(0, 9.0, SEVEN), _tree(1, 10.0, SEVEN),
         _tree(2, 11.0, SEVEN), _tree(3, 12.0, SEVEN),
         _tree(4, 20.0, SEVEN),           # 8 s: the profiler's dump
         _tree(5, 21.2, NINE, wait=1.1, dispatch=0.07),
         _tree(6, 22.2, SEVEN),
         _tree(7, None, NINE)]
WINDOW = [7, 7, 7, 9, 7, 9]


def facts_with(trees, passes=WINDOW, traced=0, rows=1000, grower=None):
    facts = Facts({"data": {"rows": rows}}, {}, {},
                  {"hist_passes": passes, "traced_trees": traced})
    facts.program_snapshot = None if trees is None else {
        "trees": trees, "grower": grower or {}}
    return facts


def test_clock_readers_time_every_window_tree_of_an_untraced_run():
    facts = facts_with(TREES)
    # periods of window trees 2..6: 1.0, 1.0, 8.0, 1.2, 1.0 (7 has no stamp)
    assert read("tree_ms_p50", facts) == pytest.approx(1000.0)
    assert read("tree_ms_max", facts) == pytest.approx(8000.0)
    assert read("host_dispatch_ms_per_tree", facts) == pytest.approx(
        1e3 * (4 * 0.05 + 0.07) / 5)
    # the wait that ended tree i's period is on tree i + 1's row
    assert read("host_wait_share", facts) == pytest.approx(
        (4 * 0.9 + 1.1) / 12.2)


def test_a_traced_run_leaves_out_the_traced_trees_and_the_one_after():
    facts = facts_with(TREES, traced=2)
    # window trees 2, 3 traced, 4 holds the dump: 5 and 6 are left
    assert read("tree_ms_p50", facts) == pytest.approx(1100.0)
    assert read("tree_ms_max", facts) == pytest.approx(1200.0)
    assert read("host_wait_share", facts) == pytest.approx(1.8 / 2.2)
    # 9 passes 1.2 s, 7 passes 1.0 s: 0.1 s a pass
    assert read("marginal_pass_ms", facts) == pytest.approx(100.0)


def test_the_slope_needs_two_distinct_pass_counts():
    same = [_tree(i, float(i), SEVEN) for i in range(6)]
    assert read("marginal_pass_ms", facts_with(same, [7] * 4)) is None
    assert read("tree_ms_p50", facts_with(same, [7] * 4)) == pytest.approx(1000.0)


def test_count_readers_sum_over_every_window_tree():
    facts = facts_with(TREES, traced=2)
    assert read("wave_rows_share", facts) == pytest.approx(0.4)
    # 6 trees x 4 passes of 200 rows + 2 trees x 2 of 100, of 1000 rows
    assert read("endgame_rows_share", facts) == pytest.approx(
        (24 * 200 + 4 * 100) / (28 * 1000))
    held = 12 * 9 + 24 * 5 + 4 * 4
    assert read("compact_blocks_active_share", facts) == pytest.approx(held / 400)
    # a grower built for a booster that samples compacts its first pass too
    sampled = facts_with(TREES, grower={"sampled": True})
    assert read("compact_blocks_active_share", sampled) == pytest.approx(
        (held + 60) / 460)
    assert read("ramp_sample_row_share", facts) == 1.0
    bagged = [dict(t, ramp_sample_rows=120) for t in TREES]
    assert read("ramp_sample_row_share", facts_with(bagged)) == pytest.approx(0.3)


def test_a_tree_without_waves_endgame_or_ramp_gives_nothing_there():
    root_and_waves = [_pass(0, 1000, 1000, leaves=1)] + [_pass(1, 300, 240)] * 6
    trees = [_tree(i, float(i), root_and_waves, ramp=(0, 0)) for i in range(6)]
    facts = facts_with(trees, [7] * 4)
    assert read("wave_rows_share", facts) == pytest.approx(0.3)
    assert read("endgame_rows_share", facts) is None
    assert read("ramp_sample_row_share", facts) is None
    assert read("marginal_pass_ms", facts) is None


@pytest.mark.parametrize("name", CLOCK + COUNTS)
def test_a_program_without_the_log_gives_nothing(name):
    """The parent of the PR that added the log: rows with neither stamps
    nor passes; rows that are not the window's; no record at all."""
    old = [{"iteration": i, "class_id": 0, "hist_passes": p}
           for i, p in enumerate([7, 7] + WINDOW)]
    assert read(name, facts_with(old)) is None
    assert read(name, facts_with(TREES, passes=[7, 7, 7, 9, 7, 8])) is None
    assert read(name, facts_with(TREES, passes=[])) is None
    assert read(name, facts_with(None)) is None


def test_class_rows_of_one_iteration_count_once():
    trees = [dict(_tree(i // 3, float(i // 3), SEVEN), class_id=i % 3)
             for i in range(18)]
    facts = facts_with(trees, [7] * 12)
    assert read("tree_ms_p50", facts) == pytest.approx(1000.0)
    assert read("tree_ms_max", facts) == pytest.approx(1000.0)


def test_the_manifest_lists_the_nine_where_they_find_something():
    assert validate.validate(helpers.REPO) == []
    m = mf.load_manifest(helpers.REPO)
    names = [p["name"] for p in m["per_layer"]]
    first = names.index(CLOCK[0])       # later PRs append their metrics behind the nine
    assert names[first:first + 9] == CLOCK + COUNTS
    for name in CLOCK + COUNTS:
        entry = mf.find_named(m["per_layer"], name, "metric")
        assert entry["moves"] == "train_iters_per_s"
        listed = NUMERIC if name in NOT_IN_CAT else CELLS
        assert entry["workloads"][:len(listed)] == listed     # and their cells behind these
        assert entry["source"] == ("program_span" if name in CLOCK
                                   else "program_counter")
    cat = {p["name"] for p in mf.metrics_for(m, "criteo-cat-q8.train", "per_layer")}
    assert not cat & NOT_IN_CAT and set(CLOCK + COUNTS) - NOT_IN_CAT <= cat


def test_a_traced_run_on_the_cpu_prints_them(tmp_path, capsys, monkeypatch):
    monkeypatch.setattr(run, "find_device", lambda chips: dict(helpers.CPU_DEVICE))
    real = roofline.load_peaks
    monkeypatch.setattr(roofline, "load_peaks",
                        lambda kind, path=None: real("TPU v5 lite"))
    root = helpers.make_root(str(tmp_path))
    extra = f"{root}/extrabench"
    with open(f"{extra}/workloads/tiny-steady.json", "w") as fh:
        # two traced trees, the one after them, then at least five timed
        json.dump(dict(helpers.TINY_MIX, min_window_trees=9), fh)
    cfg = helpers.tiny_config("tiny", quantized=True)
    cfg["params"]["tpu_wave_size"] = 4       # W = 4 of 15 leaves: a ramp,
    with open(f"{extra}/configs/tiny.json", "w") as fh:   # waves, an endgame
        json.dump(cfg, fh)
    rc = run.main(["--workload", "tiny.train", "--seed", str(2**31 + 36),
                   "--seconds", "0.5", "--trace", "1"], root=root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(CLOCK + COUNTS) - {"marginal_pass_ms"} <= set(m)
    assert 0 < m["tree_ms_p50"] <= m["tree_ms_max"]
    assert 0 < m["host_dispatch_ms_per_tree"] < m["tree_ms_max"]
    assert 0 <= m["host_wait_share"] < 1
    # the CPU's pipeline compacts nothing: every pass loops the padded rows
    assert m["endgame_rows_share"] == m["wave_rows_share"] == 8192 / 6000
    assert m["compact_blocks_active_share"] == 1.0
    assert m["ramp_sample_row_share"] == 1.0
    # the three kinds' rows are all the rows the kernels looped over
    passes = line["notes"]["hist_passes"]
    first = 8192 / 6000          # the dense first pass: the padded rows
    waves = m["hist_passes_per_tree"] - 1 - m["endgame_passes_per_tree"]
    assert m["hist_rows_contracted_share"] * sum(passes) / len(passes) == \
        pytest.approx(first + waves * m["wave_rows_share"]
                      + m["endgame_passes_per_tree"] * m["endgame_rows_share"])
