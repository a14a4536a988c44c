"""Cut a slice of a real scoped trace into the tuples that
:mod:`chipbench.scope_reduce` works on, for ``chipbench/tests/data``:

    python -m chipbench.tools.record_scopes <trace_dir or .xplane.pb> <out.json> [seconds] [start]

Kept: the device planes' operation-line events that touch ``seconds``
(default 0.25) of the ``chipbench.window`` span from ``start`` seconds into it
(default 0), as ``[device, name, scope, start_ns, end_ns]`` with the times
counted from the slice's start and the scopes as the trace gives them (before
``fill_between``); the stat's name; and the parts worked out from exactly
these tuples.  A name is cut to its instruction name plus the marker of a
loop, conditional or call, which is all the arithmetic reads.

It also prints, for looking at a trace by hand, which stats of the operations'
event metadata hold a ``lgbm.`` scope at all, and the device time of each
scope over the whole window, before and after ``fill_between``.
"""

from __future__ import annotations

import collections
import json
import os
import sys

from chipbench import scope_reduce as sr
from chipbench import trace_reduce as tr


def cut_name(name: str) -> str:
    marker = next((c for c in tr._CONTAINERS if c in name), "")
    return name.split(" = ")[0].lstrip("%") + marker


def stats_with_scopes(xspace: bytes) -> dict:
    """{stat name: event metadata whose value holds ``lgbm.``}, device planes."""
    seen: collections.Counter = collections.Counter()
    for f, plane_buf in sr._fields(memoryview(xspace)):
        if f != 1:
            continue
        plane = sr._plane(plane_buf)
        if not plane["name"].startswith(tr.DEVICE_PLANE_PREFIX):
            continue
        for stats in plane["event_stats"].values():
            for view in stats:
                fields = dict(sr._fields(view))
                if not isinstance(fields.get(5), int) and fields.get(5) is not None and \
                        sr.SCOPE_PREFIX in sr._text(fields[5]):
                    seen[plane["stat_names"].get(fields.get(1), "?")] += 1
    return dict(seen)


def by_scope(events: list) -> list:
    acc: collections.Counter = collections.Counter()
    for _, name, scope, s, e in events:
        if not sr._is_container(name):
            acc[(scope, sr.KERNEL_NEEDLE in name)] += e - s
    return sorted(acc.items(), key=lambda kv: -kv[1])


def main(argv=None) -> int:
    args = argv or sys.argv[1:]
    target, out = args[0], args[1]
    seconds = float(args[2]) if len(args) > 2 else 0.25
    start = float(args[3]) if len(args) > 3 else 0.0
    path = target if os.path.isfile(target) else tr.find_xplane(target)
    with open(path, "rb") as fh:
        xspace = fh.read()
    print(f"{path}: event-metadata stats holding a scope: {stats_with_scopes(xspace)}")
    events, window = sr.read_scoped_events(xspace)
    if window is None:
        raise SystemExit(f"{path} has no {tr.WINDOW_SPAN} span")
    for title, evs in (("as traced", events), ("after fill_between", sr.fill_between(events))):
        evs = sr.clip(evs, *window)
        print(f"  {title}:")
        for (scope, kernel), ns in by_scope(evs):
            print(f"  {ns / 1e9:9.4f}s  {scope}{' (lgbm_hist_ kernels)' if kernel else ''}")
        print(f"  parts: {json.dumps({k: v / 1e9 for k, v in sr.parts_ns(evs).items()})}")

    lo = window[0] + int(start * 1e9)
    hi = min(lo + int(seconds * 1e9), window[1])
    cut = [[dev, cut_name(name), scope, s - lo, e - lo]
           for dev, name, scope, s, e in sr.clip(events, lo, hi)]
    doc = {"stat": sr.SCOPE_STAT, "slice_ns": hi - lo, "events": cut,
           "parts_ns": sr.parts_ns(sr.fill_between([tuple(ev) for ev in cut]))}
    with open(out, "w") as fh:
        json.dump(doc, fh, separators=(",", ":"))
    print(f"{out}: {os.path.getsize(out)} bytes, {len(cut)} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
