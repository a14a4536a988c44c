"""The program's own record of the run, for the ``program_span`` and
``program_counter`` readers: ``lightgbm_tpu.telemetry.last_train_record()``,
which outlives the booster the driver deletes.  Everything here returns None
where the program keeps no such record or no such key (the parent of the PR
that added them).
"""

from __future__ import annotations


def snapshot(facts):
    """The record's snapshot, taken once a run and kept on ``facts``; None
    where the program has no record."""
    if not hasattr(facts, "program_snapshot"):
        facts.program_snapshot = None
        try:
            from lightgbm_tpu.telemetry import last_train_record
        except ImportError:
            return None
        rec = last_train_record()
        if rec is not None:
            facts.program_snapshot = rec.snapshot()
    return facts.program_snapshot


def setup_seconds(facts, key: str):
    snap = snapshot(facts)
    if not snap:
        return None
    return snap.get("setup_seconds", {}).get(key)


def window_mean(facts, key: str):
    """Mean of ``key`` over the rows of the window's trees: the record's last
    ``len(hist_passes)`` rows, checked against the passes the driver read."""
    snap = snapshot(facts)
    passes = facts.counters.get("hist_passes", [])
    if not snap or not passes or min(passes) <= 0:
        return None
    rows = snap.get("trees", [])[-len(passes):]
    if any(key not in r for r in rows) or \
            [r.get("hist_passes") for r in rows] != list(passes):
        return None
    return sum(r[key] for r in rows) / len(rows)
