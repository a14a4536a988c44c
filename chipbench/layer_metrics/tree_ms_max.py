"""The slowest tree of the window, in ms: the largest period on the program's
own tree clock (``TrainRecord`` ``done_s``), no force a tree.  One
``update()`` that returns late on a shared host shows here and nowhere else.
``tree_log.timed_rows`` says which trees a traced run leaves out."""

from chipbench.layer_metrics import tree_log


def read(facts):
    timed = tree_log.timed_rows(facts)
    return None if timed is None else 1e3 * max(p for _, p, _ in timed)
