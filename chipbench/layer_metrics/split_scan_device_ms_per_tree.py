"""Device milliseconds per traced tree under ``lgbm.wave.child_out``,
``lgbm.wave.scan``, ``lgbm.wave.commit`` and ``lgbm.endgame`` with its
``.select``: children outputs, the vmapped candidate scan, the scatter of
state and node records, the endgame's commit loop."""

from chipbench import scope_reduce


def read(facts):
    return scope_reduce.part_ms_per_tree(facts, "split_scan", __file__)
