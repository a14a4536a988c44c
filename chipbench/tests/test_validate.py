import copy
import json
import os

import pytest

from chipbench import manifest as mf
from chipbench import validate
from chipbench.tests import helpers


def test_the_shipped_manifest_passes():
    assert validate.validate(helpers.REPO) == []


def test_a_root_with_added_files_and_entries_passes(tmp_path):
    root = helpers.make_root(str(tmp_path))
    assert validate.validate(root) == []
    m = mf.load_manifest(root)
    assert "trees_in_window" in [x["name"] for x in mf.metrics_for(m, "tiny.train", "per_layer")]
    assert "trees_in_window" not in [x["name"] for x in
                                     mf.metrics_for(m, "criteo-q8.train", "per_layer")]


def _broken(tmp_path, edit):
    root = helpers.make_root(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        m = json.load(fh)
    m = copy.deepcopy(m)
    edit(m, root)
    with open(path, "w") as fh:
        json.dump(m, fh)
    return validate.validate(root)


def _set(section, index, key, value):
    def edit(m, root):
        m[section][index][key] = value
    return edit


def _drop_file(*parts):
    def edit(m, root):
        os.remove(os.path.join(root, *parts))
    return edit


BROKEN = {
    # what PR 22 died of: one character outside ASCII in one source
    "source_not_ascii": (_set("configs", -1, "source", "1.7B records × 67 features"), "printable ASCII"),
    "source_too_long": (_set("configs", -1, "source", "x" * 201), "printable ASCII"),
    "source_empty": (_set("configs", -1, "source", ""), "printable ASCII"),
    "why_with_tab": (_set("workloads", -1, "why", "a\tb"), "printable ASCII"),
    "name_with_space": (_set("per_layer", -1, "name", "trees in window"), "is not a name"),
    "name_starts_with_dash": (_set("workloads", -1, "name", "-tiny"), "is not a name"),
    "name_too_long": (_set("end_to_end", 0, "name", "n" * 65), "is not a name"),
    "unit_with_space": (_set("per_layer", -1, "unit", "trees per s"), "unit"),
    "unit_greek": (_set("per_layer", -1, "unit", "µs"), "unit"),
    "unit_too_long": (_set("per_layer", -1, "unit", "u" * 17), "unit"),
    "moves_nothing": (_set("per_layer", -1, "moves", "no_such_metric"), "no end-to-end metric"),
    "absolute_bound": (_set("end_to_end", 0, "bound", 2.5), "share of the parent's median"),
    "zero_bound": (_set("end_to_end", 0, "bound", 0), "share of the parent's median"),
    "per_cell_bound": (_set("end_to_end", 0, "bounds", {"tiny.train": 0.05}), "may not have"),
    "why_on_a_metric": (_set("per_layer", 0, "why", "because"), "may not have"),
    "bad_source_kind": (_set("per_layer", 0, "source", "guess"), "source must be one of"),
    "e2e_from_counter": (_set("end_to_end", 0, "source", "program_counter"), "source must be one of"),
    "better_sideways": (_set("end_to_end", 0, "better", "sideways"), "lower or higher"),
    "three_chips": (_set("workloads", -1, "chips", 3), "chips must be 1 or 4"),
    "unknown_config": (_set("workloads", -1, "config", "nope"), "no configuration named"),
    "unknown_cell_listed": (_set("per_layer", 0, "workloads", ["nope.train"]), "does not exist"),
    "run_seconds_too_long": (lambda m, root: m.__setitem__("run_seconds", 52), "run_seconds"),
    "extra_top_key": (lambda m, root: m.__setitem__("notes", "x"), "top-level keys"),
    "reduced_width": (_set("configs", 0, "reduced", ["rows", "features"]), "may not name a width"),
    "command_leaves_repo": (lambda m, root: m.__setitem__("command", ["python3", "../x.py"]),
                            "leaves the repo"),
    "config_file_missing": (_drop_file("extrabench", "configs", "tiny.json"), "does not exist"),
    "mix_file_missing": (_drop_file("extrabench", "workloads", "tiny-steady.json"), "no mix file"),
    "reader_file_missing": (_drop_file("extrabench", "layer_metrics", "trees_in_window.py"),
                            "no reader file"),
}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_a_broken_manifest_is_refused(tmp_path, case):
    edit, expect = BROKEN[case]
    errs = _broken(tmp_path, edit)
    assert errs and any(expect in e for e in errs), errs


def test_missing_driver_and_bad_config_file(tmp_path):
    root = helpers.make_root(str(tmp_path))
    mix = os.path.join(root, "extrabench", "workloads", "tiny-steady.json")
    with open(mix, "w") as fh:
        json.dump(dict(helpers.TINY_MIX, driver="open_loop_serve"), fh)
    assert any("no driver file" in e for e in validate.validate(root))
    with open(mix, "w") as fh:
        json.dump(helpers.TINY_MIX, fh)
    cfg = os.path.join(root, "extrabench", "configs", "tiny.json")
    bad = helpers.tiny_config("tiny", True)
    del bad["limits"]
    bad["source"] = "another source"
    with open(cfg, "w") as fh:
        json.dump(bad, fh)
    errs = validate.validate(root)
    assert any("lacks the group 'limits'" in e for e in errs)
    assert any("source differs" in e for e in errs)


def test_too_many_four_chip_cells(tmp_path):
    def edit(m, root):
        for w in m["workloads"][:2]:
            w["chips"] = 4
    assert any("ask for 4 chips" in e for e in _broken(tmp_path, edit))


def test_the_command_line_entry(capsys):
    assert validate.main([helpers.REPO]) == 0
    assert "ok" in capsys.readouterr().out
