"""Device 0's ``lgbm_hist_*`` kernel events of a traced benchmark run, in
start order: what each histogram pass of the traced trees cost (PERF.md
section 5's pass-by-pass line).

    python scripts/hist_kernel_events.py <checkout root> <out.json>

Reads the newest ``.xplane.pb`` under ``<root>/.chipbench_trace`` (a
``chipbench.run --trace 1`` leaves it there) with the benchmark's own
reader and prints ``name  duration_ms  start_ms`` inside the traced window."""

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


def main(root, out):
    sys.path.insert(0, root)
    from chipbench import trace_reduce as tr
    path = max(glob.glob(os.path.join(root, ".chipbench_trace", "*", "plugins",
                                      "profile", "*", "*.xplane.pb")),
               key=os.path.getmtime)
    rows, window_ms = kernel_events(*tr.load(path), tr)
    with open(out, "w") as fh:
        json.dump({"trace": path, "window_ms": window_ms, "events": rows}, fh)
    for row in rows:
        print(*row)


if __name__ == "__main__":
    main(*sys.argv[1:3])
