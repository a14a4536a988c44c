"""Share of the trees' periods that the host spent waiting for the device:
the waits that ended the periods (``TrainRecord`` ``wait_s``, the boosting
loop's ``wait_prev`` span around the lagged stump check; the wait for a tree
is booked on the row of the iteration after it) over the sum of the periods,
over the window's trees that the tree clock times.  One less this is the
host's own share of a tree."""

from chipbench.layer_metrics import tree_log


def read(facts):
    timed = tree_log.timed_rows(facts)
    if timed is None or any(w is None for _, _, w in timed):
        return None
    return sum(w for _, _, w in timed) / sum(p for _, p, _ in timed)
