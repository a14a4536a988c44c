"""Least time one wave's routing needs (``chipbench/roofline_row_update.py``:
each row's leaf id read and written, its channel written, one bin code read,
at this device's HBM peak) over the traced ``lgbm_wave_row_update_*`` kernel
time per call, in percent.  None where the trace holds no such kernel (a
program that routes rows some other way)."""

from chipbench import roofline_row_update

NEEDLE = "lgbm_wave_row_update_"


def read(facts):
    secs = facts.traced_kernel_s(NEEDLE)
    if secs is None:
        return None
    dev = min(ev[0] for ev in facts.trace.events)
    calls = sum(1 for d, name, _, _ in facts.trace.events if d == dev and NEEDLE in name)
    p, d = facts.config["params"], facts.config["data"]
    floor = roofline_row_update.row_update_floor(d["rows"], p["max_bin"], p["num_leaves"],
                                                 facts.peaks)
    return 100.0 * floor / (secs / calls)
