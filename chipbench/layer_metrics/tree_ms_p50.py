"""Median period of the window's trees, in ms, on the program's own tree
clock (``TrainRecord`` ``done_s``: when a tree's ``num_leaves`` reached the
host, stamped after the one wait a boosting iteration has): all the window's
trees, not the three the device trace holds.  ``tree_log.timed_rows`` says
which trees a traced run leaves out."""

import statistics

from chipbench.layer_metrics import tree_log


def read(facts):
    timed = tree_log.timed_rows(facts)
    return None if timed is None else 1e3 * statistics.median(p for _, p, _ in timed)
