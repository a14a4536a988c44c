"""Print what a recorded ``.xplane.pb`` holds: planes, lines, how many events
each, and the commonest event names with their stats.  For looking at a trace
by hand before trusting ``trace_reduce``:

    python -m chipbench.tools.dump_trace <trace_dir or file.xplane.pb>
"""

from __future__ import annotations

import collections
import os
import sys

from chipbench import trace_reduce


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    target = (argv or sys.argv[1:])[0]
    path = target if os.path.isfile(target) else trace_reduce.find_xplane(target)
    prof = ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in prof.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter(ev.name for ev in events)
            total = sum(ev.duration_ns for ev in events)
            print(f"  line {line.name!r}: {len(events)} events, {total / 1e9:.4f}s summed")
            shown = 0
            for name, cnt in names.most_common(12):
                ev = next(e for e in events if e.name == name)
                stats = {k: (v if not isinstance(v, str) else v[:160]) for k, v in ev.stats}
                print(f"    {cnt:6d} x {name[:100]!r} first: start {ev.start_ns} dur {ev.duration_ns} "
                      f"stats {stats if shown < 4 else '...'}")
                shown += 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
