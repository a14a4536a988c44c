"""``python -m chipbench.validate``: the manifest and every data file it names,
checked here in the sandbox before any chip time is spent.

It holds ``BENCHMARK.json`` to the rules a check applies before its first run
(names, units, lengths, printable ASCII, the keys an entry may have, bounds,
the share of four-chip cells) and to what the harness itself needs (every
cell's configuration, mix, driver and metric-reader files exist; every
per-layer metric moves an end-to-end metric that each of its cells reports;
every configuration states limits for ``correct``).
"""

from __future__ import annotations

import json
import os
import re
import sys

from . import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"}, {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"}, {"workloads"}),
}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection", "head", "expansion",
               "features", "max_bin", "num_leaves", "experts_per")
MAX_BYTES = 64 * 1024


def _line(text, what: str, errs: list, limit: int = 200) -> None:
    """1 to ``limit`` printable ASCII characters on one line."""
    if not isinstance(text, str) or not 1 <= len(text) <= limit or \
            any(not 32 <= ord(c) < 127 for c in text):
        errs.append(f"{what} must be 1 to {limit} printable ASCII characters on one line")


def _name(text, what: str, errs: list) -> None:
    if not isinstance(text, str) or not NAME.match(text):
        errs.append(f"{what}: {text!r} is not a name (letters, digits, _ . -, at most 64, "
                    f"not starting with . or -)")


def _unique(entries: list, what: str, errs: list) -> None:
    seen = set()
    for e in entries:
        n = e.get("name")
        if n in seen:
            errs.append(f"{what}: the name {n!r} appears twice")
        seen.add(n)


def _keys(entry: dict, kind: str, errs: list) -> None:
    need, may = KEYS[kind]
    have = set(entry)
    if need - have:
        errs.append(f"{kind} entry {entry.get('name')!r} lacks {sorted(need - have)}")
    if have - need - may:
        errs.append(f"{kind} entry {entry.get('name')!r} has keys it may not have: "
                    f"{sorted(have - need - may)}")


def validate(root: str) -> list:
    """Every fault found, as text; an empty list passes."""
    errs: list = []
    path = os.path.join(root, mf.MANIFEST)
    if os.path.getsize(path) > MAX_BYTES:
        errs.append(f"{mf.MANIFEST} is over 64 KiB")
    try:
        m = mf.load_json(path)
    except (OSError, json.JSONDecodeError) as exc:
        return [f"{mf.MANIFEST} cannot be read: {exc}"]
    if set(m) != TOP_KEYS:
        errs.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}, not {sorted(m)}")
        return errs

    # command, paths, run_seconds
    cmd = m["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        errs.append("command must be a list of 1 to 32 strings")
    else:
        for word in cmd:
            _line(word, f"command word {word!r}", errs)
            if isinstance(word, str) and (word.startswith("/") or ".." in word.split("/")):
                errs.append(f"command word {word!r} leaves the repo")
    paths = m["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        errs.append("paths must list 1 to 16 directories")
        paths = []
    for p in paths:
        if not isinstance(p, str) or not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            errs.append(f"path {p!r} is not a relative path of letters, digits, _ . - /")
        elif not os.path.isdir(os.path.join(root, p)):
            errs.append(f"path {p!r} is not a directory")
    rs = m["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= 51:
        errs.append("run_seconds must be a whole number from 1 to 51")

    for kind, lo, hi in (("configs", 1, 24), ("workloads", 1, 24), ("end_to_end", 1, 16),
                         ("per_layer", 1, 128)):
        if not isinstance(m[kind], list) or not lo <= len(m[kind]) <= hi:
            errs.append(f"{kind} must have {lo} to {hi} entries")
            return errs
        for e in m[kind]:
            if not isinstance(e, dict):
                errs.append(f"{kind} holds something that is not an object")
                return errs
            _keys(e, kind, errs)
            _name(e.get("name"), f"{kind} name", errs)
        _unique(m[kind], kind, errs)
    _unique(m["end_to_end"] + m["per_layer"], "metrics", errs)
    if errs:
        return errs

    cells = {w["name"]: w for w in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {e["name"]: e for e in m["end_to_end"]}

    # configurations and their files
    files = set()
    for c in m["configs"]:
        what = f"config {c['name']}"
        _line(c["source"], f"{what}: source", errs)
        _line(c["why"], f"{what}: why", errs)
        f = c["file"]
        if not isinstance(f, str) or not PATH.match(f) or \
                not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            errs.append(f"{what}: file {f!r} does not lie under paths")
        elif f in files:
            errs.append(f"{what}: file {f!r} is another configuration's too")
        elif not os.path.isfile(os.path.join(root, f)):
            errs.append(f"{what}: file {f!r} does not exist")
        else:
            errs.extend(_config_file(os.path.join(root, f), c))
        files.add(f)
        red = c["reduced"]
        if not isinstance(red, list) or len(red) > 16:
            errs.append(f"{what}: reduced must be a list of at most 16 keys")
        else:
            for key in red:
                _name(key, f"{what}: reduced key", errs)
                if isinstance(key, str) and (key.endswith(("_dim", "_rank")) or
                                             any(wd in key for wd in WIDTH_WORDS)):
                    errs.append(f"{what}: reduced may not name a width or a shape ({key!r})")
        if not any(w["config"] == c["name"] for w in m["workloads"]):
            errs.append(f"{what}: no cell uses it")

    # cells, their mixes and drivers
    pairs = set()
    for w in m["workloads"]:
        what = f"workload {w['name']}"
        _name(w["config"], f"{what}: config", errs)
        _name(w["traffic"], f"{what}: traffic", errs)
        _line(w["why"], f"{what}: why", errs)
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            errs.append(f"{what}: chips must be 1 or 4")
        if w["config"] not in configs:
            errs.append(f"{what}: no configuration named {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"{what}: the pair of configuration and traffic appears twice")
        pairs.add((w["config"], w["traffic"]))
        try:
            mix_path = mf.mix_file(root, m, w["traffic"])
        except FileNotFoundError:
            errs.append(f"{what}: no mix file workloads/{w['traffic']}.json under paths")
            continue
        try:
            mix = mf.load_json(mix_path)
        except json.JSONDecodeError as exc:
            errs.append(f"{what}: mix file is not JSON: {exc}")
            continue
        _name(mix.get("driver"), f"{what}: the mix's driver", errs)
        try:
            mf.driver_file(root, m, str(mix.get("driver")))
        except FileNotFoundError:
            errs.append(f"{what}: no driver file drivers/{mix.get('driver')}.py under paths")
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    if four > max(1, len(m["workloads"]) // 4):
        errs.append(f"{four} cells ask for 4 chips; at most a quarter of the cells, or one, may")

    # metrics
    if "setup_s" not in e2e:
        errs.append("end_to_end must have setup_s")
    for e in m["end_to_end"]:
        what = f"end_to_end {e['name']}"
        errs.extend(_metric_common(e, what, cells, E2E_SOURCES))
        b = e["bound"]
        if isinstance(b, bool) or not isinstance(b, (int, float)) or not 0 < b <= 0.1:
            errs.append(f"{what}: bound is a share of the parent's median, over 0 and at most 0.1 "
                        f"(never absolute, never per cell), not {b!r}")
    for p in m["per_layer"]:
        what = f"per_layer {p['name']}"
        errs.extend(_metric_common(p, what, cells, SOURCES))
        _line(p["layer"], f"{what}: layer", errs)
        if p["moves"] not in e2e:
            errs.append(f"{what}: moves {p['moves']!r}, which is no end-to-end metric")
            continue
        moved_in = set(mf.cells_of(e2e[p["moves"]], m))
        for cell in mf.cells_of(p, m):
            if cell in cells and cell not in moved_in:
                errs.append(f"{what}: cell {cell!r} does not report {p['moves']!r}")
        try:
            mf.metric_file(root, m, p["name"])
        except FileNotFoundError:
            errs.append(f"{what}: no reader file layer_metrics/{p['name']}.py under paths")
    for name in cells:
        own_e2e = [x["name"] for x in mf.metrics_for(m, name, "end_to_end")]
        if "setup_s" not in own_e2e or len(own_e2e) < 2:
            errs.append(f"workload {name}: must report setup_s and one more end-to-end metric")
        if not mf.metrics_for(m, name, "per_layer"):
            errs.append(f"workload {name}: must report a per-layer metric")
    return errs


def _metric_common(metric: dict, what: str, cells: dict, sources: tuple) -> list:
    errs: list = []
    if not isinstance(metric["unit"], str) or not UNIT.match(metric["unit"]):
        errs.append(f"{what}: unit {metric['unit']!r} is not 1 to 16 of letters, digits, _ / % . -")
    if metric["better"] not in ("lower", "higher"):
        errs.append(f"{what}: better must be lower or higher")
    if metric["source"] not in sources:
        errs.append(f"{what}: source must be one of {sources}")
    if "workloads" in metric:
        if not isinstance(metric["workloads"], list) or not metric["workloads"]:
            errs.append(f"{what}: workloads must be a list of cells")
        else:
            for c in metric["workloads"]:
                if c not in cells:
                    errs.append(f"{what}: lists the cell {c!r}, which does not exist")
    return errs


def _config_file(path: str, entry: dict) -> list:
    """A configuration's own file: what the harness and the check read of it."""
    what = f"config {entry['name']}: file"
    try:
        cfg = mf.load_json(path)
    except json.JSONDecodeError as exc:
        return [f"{what} is not JSON: {exc}"]
    errs: list = []
    if not path.endswith(DATA_SUFFIXES):
        errs.append(f"{what} is not a data file ({DATA_SUFFIXES})")
    if "source" in cfg:
        _line(cfg["source"], f"{what}: source", errs)
        if cfg["source"] != entry["source"]:
            errs.append(f"{what}: source differs from the manifest's")
    if "name" in cfg and cfg["name"] != entry["name"]:
        errs.append(f"{what}: name {cfg['name']!r} differs from the manifest's")
    if sorted(cfg.get("reduced", entry["reduced"])) != sorted(entry["reduced"]):
        errs.append(f"{what}: reduced differs from the manifest's")
    for group in ("params", "data", "limits"):
        if not isinstance(cfg.get(group), dict) or not cfg[group]:
            errs.append(f"{what}: lacks the group {group!r}")
    for name, limit in (cfg.get("limits") or {}).items():
        _name(name, f"{what}: limit name", errs)
        if isinstance(limit, bool) or not isinstance(limit, (int, float)) or limit < 0:
            errs.append(f"{what}: limit {name!r} must be a number >= 0")
    return errs


def main(argv=None) -> int:
    root = (argv or sys.argv[1:] or [mf.repo_root()])[0]
    errs = validate(root)
    for e in errs:
        print(f"chipbench.validate: {e}", file=sys.stderr)
    print(f"chipbench.validate: {'FAILED, ' + str(len(errs)) + ' faults' if errs else 'ok'} ({root})")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
