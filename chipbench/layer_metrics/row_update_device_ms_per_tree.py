"""Device milliseconds per traced tree under ``lgbm.wave.row_update`` and
``lgbm.endgame.row_update``: the winning features' columns, ``row_leaf`` and
the wave-channel update, the ``lgbm_wave_row_update_*`` kernels included."""

from chipbench import scope_reduce


def read(facts):
    return scope_reduce.part_ms_per_tree(facts, "row_update", __file__)
