"""Device milliseconds per traced tree under ``lgbm.renew`` (q8 leaf renewal,
its ``lgbm_hist_single_*`` kernel left out) and ``lgbm.score_update``."""

from chipbench import scope_reduce


def read(facts):
    return scope_reduce.part_ms_per_tree(facts, "score_renew", __file__)
