"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's file is
the ``file`` of its ``configs`` entry; the mix is
``<path>/workloads/<traffic>.json`` under one of ``paths`` and names its
driver, ``<path>/drivers/<driver>.py``; a per-layer metric is read by
``<path>/layer_metrics/<name>.py``.  Nothing here knows a cell, a mix or a
metric by name, so a later PR adds any of them by adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os

MANIFEST = "BENCHMARK.json"


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_manifest(root: str) -> dict:
    return load_json(os.path.join(root, MANIFEST))


def find_named(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry.get("name") == name:
            return entry
    raise KeyError(f"{MANIFEST} has no {what} named {name!r}")


def find_file(root: str, manifest: dict, *parts: str) -> str:
    """``<path>/<parts...>`` under the first of ``paths`` that has it."""
    tried = []
    for base in manifest["paths"]:
        path = os.path.join(root, base, *parts)
        if os.path.isfile(path):
            return path
        tried.append(path)
    raise FileNotFoundError(f"none of {tried} exists")


def mix_file(root: str, manifest: dict, traffic: str) -> str:
    return find_file(root, manifest, "workloads", traffic + ".json")


def driver_file(root: str, manifest: dict, driver: str) -> str:
    return find_file(root, manifest, "drivers", driver + ".py")


def metric_file(root: str, manifest: dict, metric: str) -> str:
    return find_file(root, manifest, "layer_metrics", metric + ".py")


def load_module(path: str):
    """Import a driver or a metric reader from its file."""
    name = "chipbench_file_" + "".join(c if c.isalnum() else "_" for c in os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cells_of(metric: dict, manifest: dict) -> list:
    """The cells in which a metric is reported: its ``workloads`` key, or every
    cell that reports the end-to-end metric it moves (itself, for an
    end-to-end metric)."""
    if "workloads" in metric:
        return list(metric["workloads"])
    target = metric.get("moves") or metric["name"]
    e2e = find_named(manifest["end_to_end"], target, "end-to-end metric")
    if "workloads" in e2e:
        return list(e2e["workloads"])
    return [w["name"] for w in manifest["workloads"]]


def metrics_for(manifest: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    return [m for m in manifest[kind] if cell in cells_of(m, manifest)]
