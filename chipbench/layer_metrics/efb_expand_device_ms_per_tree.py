"""Device milliseconds per traced tree inside what turns bundle-space
histograms into the split scan's input: per device, the union of the
operations whose innermost ``lgbm.`` scope is ``lgbm.wave.efb_expand`` (the
static slices of ``lightgbm_tpu/efb.py`` ``make_scan_expand``, inside the wave
grower's ``lgbm.wave.scan`` and the root's), averaged over the devices.  Read
from the run's ``.xplane.pb`` with ``scope_reduce``'s own functions; its table
of parts does not know the scope, so the same time sits inside
``unscoped_device_ms_per_tree`` (and not in ``split_scan_``).  None where the
program names no such scope (a data set that is not bundled, or the parent of
the PR that added it)."""

import os

from chipbench import scope_reduce
from chipbench import trace_reduce as tr

SCOPE = "lgbm.wave.efb_expand"


def scope_ns(events: list, scope: str = SCOPE) -> float:
    """Mean over the devices seen of the union of the events under ``scope``
    (loops, conditionals and calls left out, as in ``scope_reduce``)."""
    devs = sorted({ev[0] for ev in events})
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        total += sum(e - s for s, e in tr.merged_intervals(
            [(s, e) for dev, name, sc, s, e in events
             if dev == d and sc == scope and not scope_reduce._is_container(name)]))
    return total / len(devs)


def read(facts):
    k = facts.counters.get("traced_trees", 0)
    if facts.trace is None or k <= 0:
        return None
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    path = scope_reduce.newest_xplane(root)
    if path is None:
        return None
    with open(path, "rb") as fh:
        events, window = scope_reduce.read_scoped_events(fh.read())
    # whole nanoseconds worked out as ProfileData works them out: equal or not this run's
    if window is None or tuple(window) != tuple(facts.trace.window):
        return None
    ns = scope_ns(scope_reduce.clip(scope_reduce.fill_between(events), *facts.trace.window))
    return ns / 1e6 / k if ns > 0 else None
