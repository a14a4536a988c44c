"""What the readers of the program's own log of its trees share
(``TrainRecord.snapshot()["trees"]``: the tree clock ``done_s`` / ``wait_s`` /
``dispatch_s`` and the pass log ``passes``).  No metric of its own.  Every
function returns None where the program keeps no such record or no such key
(the parent of the PR that added them)."""

from chipbench import program_record


def window_rows(facts):
    """The record's rows of the window's trees: its last ``len(hist_passes)``
    rows, checked against the passes the driver read."""
    snap = program_record.snapshot(facts)
    passes = facts.counters.get("hist_passes", [])
    if not snap or not passes:
        return None
    rows = snap.get("trees", [])[-len(passes):]
    if [r.get("hist_passes") for r in rows] != list(passes):
        return None
    return rows


def timed_rows(facts):
    """``[(row, period in seconds, wait in seconds), ...]`` of the window's
    trees that the tree clock times: a tree's period is its ``done_s`` less
    the ``done_s`` of the tree before it (the last warm-up tree's, for the
    window's first); the wait is the one that ended with its stamp, which
    the NEXT iteration made and booked (that row's ``wait_s``), so it lies
    wholly inside the period.  A traced run holds the profiler's dump inside
    its window, so the ``traced_trees`` first window trees and the one after
    them are left out there, as is any tree one of whose two stamps is
    missing (the newest tree: nothing has waited for it yet).  None where
    nothing is left."""
    rows = window_rows(facts)
    if rows is None:
        return None
    trees = program_record.snapshot(facts)["trees"]
    traced = facts.counters.get("traced_trees", 0)
    first = len(trees) - len(rows) + (traced + 1 if traced else 0)
    out = []
    for i in range(max(first, 1), len(trees)):
        row, before = trees[i], trees[i - 1].get("done_s")
        if row.get("class_id") or before is None or row.get("done_s") is None:
            continue
        after = trees[i + 1] if i + 1 < len(trees) else {}
        out.append((row, row["done_s"] - before, after.get("wait_s")))
    return out or None


def passes_of(facts, kinds):
    """The pass-log entries of the window's trees whose ``kind`` is in
    ``kinds`` (0 the first pass, 1 a wave, 2 an endgame pass)."""
    rows = window_rows(facts)
    if rows is None or any("passes" not in r for r in rows):
        return None
    return [p for r in rows for p in r["passes"] if p["kind"] in kinds]


def rows_share(facts, kind):
    """Rows the leaf kernels looped over in the passes of one kind, over
    those passes times the configuration's rows.  None where the window
    holds no such pass."""
    passes = passes_of(facts, (kind,))
    if not passes:
        return None
    return sum(p["rows"] for p in passes) / (len(passes) * facts.config["data"]["rows"])
