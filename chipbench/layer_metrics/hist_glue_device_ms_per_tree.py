"""Device milliseconds per traced tree around the histogram kernels, the
kernels themselves left out: ``lgbm.root``, ``lgbm.ramp``, ``lgbm.wave.hist``
and ``lgbm.endgame.hist`` (operand preparation, dequantisation, sibling
subtraction, the ramp's subsample gather and commit tests)."""

from chipbench import scope_reduce


def read(facts):
    return scope_reduce.part_ms_per_tree(facts, "hist_glue", __file__)
