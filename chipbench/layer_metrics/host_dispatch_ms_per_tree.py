"""Host time a tree costs outside its one wait, in ms: the mean of
``TrainRecord`` ``dispatch_s`` (an iteration's gradients + sample + grow +
record phases: enqueueing) over the window's trees that the tree clock
times.  How short a tree may get before the host sets the pace."""

from chipbench.layer_metrics import tree_log


def read(facts):
    timed = tree_log.timed_rows(facts)
    if timed is None or any(r.get("dispatch_s") is None for r, _, _ in timed):
        return None
    return 1e3 * sum(r["dispatch_s"] for r, _, _ in timed) / len(timed)
