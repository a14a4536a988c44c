"""Cut a small recorded trace out of a real ``.xplane.pb``, as a text-proto
XSpace that ``jax.profiler.ProfileData.from_text_proto`` reads back:

    python -m chipbench.tools.record_trace <trace_dir or .xplane.pb> <out.textproto> [seconds]

Kept: the device planes' operation lines and the harness's own host spans,
from the start of ``chipbench.window`` for ``seconds`` (default 0.6) of it; the
window span is cut to that length.  Names are kept, times are kept to the
nanosecond, everything else is dropped.
"""

from __future__ import annotations

import os
import sys

from chipbench import trace_reduce


def _q(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ") + '"'


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    args = argv or sys.argv[1:]
    target, out = args[0], args[1]
    seconds = float(args[2]) if len(args) > 2 else 0.6
    path = target if os.path.isfile(target) else trace_reduce.find_xplane(target)
    prof = ProfileData.from_file(path)
    _, spans = trace_reduce.read_events(prof)
    lo, _ = trace_reduce.window_of(spans)
    hi = lo + int(seconds * 1e9)

    planes = []
    for pid, plane in enumerate(prof.planes):
        device = plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX)
        meta, lines = {}, []
        for lid, line in enumerate(plane.lines):
            if device and line.name != trace_reduce.OPS_LINE:
                continue
            events = []
            for ev in line.events:
                s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if device:
                    if e <= lo or s >= hi:
                        continue
                elif not ev.name.startswith(trace_reduce.SPAN_PREFIX):
                    continue
                elif ev.name == trace_reduce.WINDOW_SPAN:
                    e = min(e, hi)
                elif e <= lo or s >= hi:
                    continue
                events.append((meta.setdefault(ev.name, len(meta) + 1), s, e - s))
            if events:
                lines.append((lid + 1, line.name, events))
        if lines:
            planes.append((pid + 1, plane.name, lines, meta))

    with open(out, "w") as fh:
        for pid, pname, lines, meta in planes:
            fh.write(f"planes {{\n  id: {pid}\n  name: {_q(pname)}\n")
            for lid, lname, events in lines:
                base = min(s for _, s, _ in events)
                fh.write(f"  lines {{\n    id: {lid}\n    name: {_q(lname)}\n"
                         f"    timestamp_ns: {base}\n")
                for mid, s, d in events:
                    fh.write(f"    events {{ metadata_id: {mid} offset_ps: {(s - base) * 1000} "
                             f"duration_ps: {d * 1000} }}\n")
                fh.write("  }\n")
            for name, mid in meta.items():
                fh.write(f"  event_metadata {{ key: {mid} value {{ id: {mid} name: {_q(name)} }} }}\n")
            fh.write("}\n")
    print(f"{out}: {os.path.getsize(out)} bytes, {sum(len(e) for p in planes for _, _, e in p[2])} events")
    return 0


if __name__ == "__main__":
    sys.exit(main())
