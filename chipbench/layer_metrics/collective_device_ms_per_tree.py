"""Device milliseconds per traced tree inside the data-parallel learner's
collectives: per device, the union of the operations whose innermost
``lgbm.`` scope starts with ``lgbm.dp.`` (``lgbm.dp.hist_reduce``, the one
histogram merge a pass makes; ``lgbm.dp.exchange``, the winner exchange;
``lgbm.dp.scalar``), averaged over the devices.  A collective ends when the
slowest shard has arrived, so this holds the wait for it: the point.  Read
from the run's ``.xplane.pb`` as ``scope_reduce`` reads it; None where the
program names no such scope (one chip, or the parent of the PR that added
them)."""

import os

from chipbench import scope_reduce
from chipbench import trace_reduce as tr

SCOPE_PREFIX = "lgbm.dp."


def collective_ns(events: list) -> float:
    """Mean over the devices seen of the union of the ``lgbm.dp.*`` events
    (loops, conditionals and calls left out, as in ``scope_reduce``)."""
    devs = sorted({ev[0] for ev in events})
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        total += sum(e - s for s, e in tr.merged_intervals(
            [(s, e) for dev, name, scope, s, e in events
             if dev == d and scope and scope.startswith(SCOPE_PREFIX)
             and not scope_reduce._is_container(name)]))
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
    ns = collective_ns(scope_reduce.clip(events, *facts.trace.window))
    return ns / 1e6 / k if ns > 0 else None
