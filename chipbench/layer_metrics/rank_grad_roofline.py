"""Least time one lambdarank gradient step needs (``chipbench/roofline_rank.py``:
the cell's rows and queries, HBM bytes alone, as the chip has no published
VPU peak) over the measured device time of the gradient program per traced
tree (``rank_grad_device_ms_per_tree``: one step a tree), in percent."""

from chipbench import roofline_rank
from chipbench.layer_metrics.rank_grad_device_ms_per_tree import traced_ms


def read(facts):
    ms = traced_ms(facts)
    if ms is None:
        return None
    d = facts.config["data"]
    return 100.0 * roofline_rank.gradient_floor(d["rows"], d["queries"], facts.peaks) / (ms / 1e3)
