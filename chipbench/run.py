"""``python -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One process, no children.  Finds the cell in ``BENCHMARK.json``, its
configuration, its traffic mix and the mix's driver by name, refuses to run
without the chips the cell asks for, lets the driver set up, measure and
check, and prints the contract's one result line last on standard output.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()      # as near to process start as this module gets

import argparse
import json
import os
import sys

from . import manifest as mf


def find_device(chips: int) -> dict:
    """The device as JAX reports it; no TPU, or too few chips, ends the run."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"chipbench measures the chip; JAX reports platform="
                         f"{dev['platform']!r}. Not running.")
    if dev["count"] < chips:
        raise SystemExit(f"cell needs {chips} chips; JAX reports {dev['count']}")
    return dev


class Run:
    """What a driver gets: the cell's files, the arguments, the clock."""

    def __init__(self, root, manifest, cell, config, mix, device, seed, seconds, trace):
        self.root, self.manifest, self.cell = root, manifest, cell
        self.config, self.mix, self.device = config, mix, device
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t0 = _T0

    def log(self, msg: str) -> None:
        print(f"[chipbench +{time.perf_counter() - self.t0:7.1f}s] {msg}",
              file=sys.stderr, flush=True)


def layer_metrics(run: Run, facts) -> dict:
    """Each per-layer metric of this cell, read by its own file.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in mf.metrics_for(run.manifest, run.cell["name"], "per_layer"):
        reader = mf.load_module(mf.metric_file(run.root, run.manifest, m["name"]))
        value = reader.read(facts)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = root or mf.repo_root()
    manifest = mf.load_manifest(root)
    cell = mf.find_named(manifest["workloads"], args.workload, "workload")
    cfg_entry = mf.find_named(manifest["configs"], cell["config"], "config")
    config = mf.load_json(os.path.join(root, cfg_entry["file"]))
    mix = mf.load_json(mf.mix_file(root, manifest, cell["traffic"]))
    driver = mf.load_module(mf.driver_file(root, manifest, mix["driver"]))
    device = find_device(int(cell["chips"]))

    run = Run(root, manifest, cell, config, mix, device, args.seed, args.seconds,
              bool(args.trace))
    res = driver.run(run)          # -> dict: e2e, facts, attempted, failed, correct, checks, ...

    if run.trace:
        metrics = layer_metrics(run, res["facts"])
    else:
        wanted = mf.metrics_for(manifest, cell["name"], "end_to_end")
        metrics = {m["name"]: {"value": float(res["end_to_end"][m["name"]]), "unit": m["unit"]}
                   for m in wanted}
    dev_out = dict(device, memory_peak_bytes=int(res["memory_peak_bytes"]))
    line = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics, "device": dev_out}
    if run.trace:
        dev_out["busy_s"] = res["facts"].trace.busy_s
        dev_out["window_s"] = res["facts"].trace.window_s
        line["breakdown"] = res["facts"].trace.breakdown()
    line["notes"] = res.get("notes", {})
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in res["checks"]}
    for name, value, limit in res["checks"]:
        print(f"check {name} value {value!r} limit {limit!r} "
              f"{'ok' if value <= limit else 'FAILED'}", file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
