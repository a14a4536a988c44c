"""Least time one full-data pass needs (``chipbench/roofline.py``: the cell's
shapes and stated precision, this device's peaks) over the measured histogram
kernel time per pass, in percent.  At these shapes HBM bytes bound it."""

from chipbench import roofline


def read(facts):
    secs, passes = facts.traced_kernel_s("lgbm_hist_"), facts.traced_passes()
    if secs is None or passes is None:
        return None
    p, d = facts.config["params"], facts.config["data"]
    floor = roofline.pass_floor(d["rows"], d["features"], p["max_bin"], p["num_leaves"],
                                facts.config["hist_precision"], facts.peaks)
    return 100.0 * floor["seconds"] / (secs / passes)
