"""Device-busy milliseconds per traced tree under no ``lgbm.`` scope and in no
histogram kernel: the instrumentation's own gap (eager per-tree operations,
time inside a loop that no operation covers)."""

from chipbench import scope_reduce


def read(facts):
    return scope_reduce.part_ms_per_tree(facts, scope_reduce.UNSCOPED, __file__)
