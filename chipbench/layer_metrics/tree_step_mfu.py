"""The whole step's share of the chip's peak: what a tree cannot do without,
one full-data pass at the roofline (every tree's root histogram reads every
bin once), over the traced window's time per tree, idle time included.  It
stays readable when a later PR renames or removes the histogram kernels."""

from chipbench import roofline


def read(facts):
    k = facts.counters.get("traced_trees", 0)
    if facts.trace is None or k <= 0 or facts.trace.window_s <= 0:
        return None
    p, d = facts.config["params"], facts.config["data"]
    floor = roofline.pass_floor(d["rows"], d["features"], p["max_bin"], p["num_leaves"],
                                facts.config["hist_precision"], facts.peaks)
    return 100.0 * floor["seconds"] * k / facts.trace.window_s
