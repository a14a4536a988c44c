"""What one more full-data histogram pass costs a tree, in ms: the
least-squares slope of the trees' periods (``TrainRecord`` ``done_s``) on
their ``hist_passes``, over the window's trees that the tree clock times.
None where they all took the same number of passes."""

from chipbench.layer_metrics import tree_log


def read(facts):
    timed = tree_log.timed_rows(facts)
    if timed is None or len({r["hist_passes"] for r, _, _ in timed}) < 2:
        return None
    x = [r["hist_passes"] for r, _, _ in timed]
    y = [p for _, p, _ in timed]
    mx, my = sum(x) / len(x), sum(y) / len(y)
    return 1e3 * (sum((a - mx) * (b - my) for a, b in zip(x, y))
                  / sum((a - mx) ** 2 for a in x))
