"""Device 0's ``lgbm_hist_*`` kernel events of a traced benchmark run, in
start order, each beside the pass-log entry it belongs to: what each
histogram pass of the traced trees cost and what it was (PERF.md section 5's
pass-by-pass lines).

    python scripts/hist_kernel_events.py <checkout root> <out.json> [<record.json>]

Reads the newest ``.xplane.pb`` under ``<root>/.chipbench_trace`` (a
``chipbench.run --trace 1`` leaves it there) with the benchmark's own
reader and prints ``name  duration_ms  start_ms`` inside the traced window.
With ``<record.json>`` (``scripts/cell_record.py`` of the same run) every
event of a counted pass is followed by ``tree pass kind leaves rows
active_rows blocks blocks_active`` of its entry in the program's pass log
(``TrainRecord.snapshot()["trees"][i]["passes"]``; under a mesh the log's
counts are sums over the row shards, the events device 0's).  A counted
pass is the kernels at the data's full length: the compaction's plan and
move and the leaf kernel behind them, or a dense leaf kernel (several
``_seg`` calls where the grower sums a pass in segments); the ramp's
subsample passes and the renewal pass are no counted passes and stay bare."""

import glob
import json
import os
import re
import sys


def kernel_events(device_events, host_spans, tr):
    """([name, duration_ms, start_ms], ...) of the first device, and the
    traced window's length in ms."""
    lo, hi = tr.window_of(host_spans)
    first = min(e[0] for e in device_events)
    rows = []
    for dev, name, start, end in sorted(tr.clip(device_events, lo, hi),
                                        key=lambda e: e[2]):
        kernel = re.search(r"lgbm_hist_[a-z0-9_]+", name)
        if dev == first and kernel:
            rows.append([kernel.group(0), round((end - start) / 1e6, 3),
                         round((start - lo) / 1e6, 1)])
    return rows, (hi - lo) / 1e6


def pass_of_each(names):
    """For each kernel name the index of the counted pass it belongs to, or
    None: see the module's docstring for what a counted pass is."""
    lanes = [int(re.search(r"_n(\d+)$", n).group(1)) for n in names]
    full = max(lanes) // 2          # the subsample is far shorter
    out, cur, state = [], -1, "closed"
    for name, n in zip(names, lanes):
        counted = n > full and ("hist_compact" in name or "hist_leaves" in name)
        if not counted:
            out.append(None)
            state = "closed"
            continue
        if "hist_compact_plan" in name:
            cur, state = cur + 1, "compacting"
        elif "hist_leaves" in name:
            more = state == "leaves" and "_seg" in name
            if state != "compacting" and not more:
                cur += 1
            state = "leaves"
        out.append(cur)
    return out


def window_passes(record_path, traced):
    """``[(tree, pass index, entry), ...]`` of the traced trees: the
    window's first ``traced`` rows of the record."""
    with open(record_path) as fh:
        doc = json.load(fh)
    n = len(doc["line"]["notes"]["hist_passes"])
    rows = doc["record"]["trees"][-n:][:traced]
    return [(r["iteration"], i, p) for r in rows
            for i, p in enumerate(r["passes"])]


def main(root, out, record=None):
    sys.path.insert(0, root)
    from chipbench import trace_reduce as tr
    path = max(glob.glob(os.path.join(root, ".chipbench_trace", "*", "plugins",
                                      "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    rows, window_ms = kernel_events(*tr.load(path), tr)
    if record:
        with open(record) as fh:
            traced = json.load(fh)["line"]["notes"]["traced_trees"]
        log = window_passes(record, traced)
        for row, k in zip(rows, pass_of_each([r[0] for r in rows])):
            if k is not None and k < len(log):
                tree, i, p = log[k]
                row += [tree, i, p["kind"], p["leaves"], p["rows"],
                        p["active_rows"], p["blocks"], p["blocks_active"]]
    with open(out, "w") as fh:
        json.dump({"trace": path, "window_ms": window_ms, "events": rows,
                   "columns": ["name", "ms", "start_ms", "tree", "pass",
                               "kind", "leaves", "rows", "active_rows",
                               "blocks", "blocks_active"]}, fh)
    for row in rows:
        print(*row)


if __name__ == "__main__":
    main(*sys.argv[1:4])
