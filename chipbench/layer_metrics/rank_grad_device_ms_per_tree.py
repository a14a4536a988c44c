"""Device milliseconds per traced tree inside the lambdarank objective's
gradient program: per device, the union of the operations whose innermost
``lgbm.`` scope is ``lgbm.rank.lambdas`` (``lightgbm_tpu/objective/rank.py``:
the gathers into the length buckets, the sorts, the truncated pair blocks,
the gather back to row order), averaged over the devices.  One gradient step
a tree.  Read from the run's ``.xplane.pb`` with ``scope_reduce``'s own
functions; its table of parts does not know the scope, so the same time
sits inside ``unscoped_device_ms_per_tree``.  None where the program names no
such scope (the parent of the PR that added it)."""

import os

from chipbench import scope_reduce
from chipbench.layer_metrics.efb_expand_device_ms_per_tree import scope_ns

SCOPE = "lgbm.rank.lambdas"


def traced_ms(facts):
    """The scope's device milliseconds per traced tree, or None."""
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
    ns = scope_ns(scope_reduce.clip(scope_reduce.fill_between(events), *facts.trace.window),
                  SCOPE)
    return ns / 1e6 / k if ns > 0 else None


def read(facts):
    return traced_ms(facts)
