"""``tree_step_mfu`` for a sampled tree: one pass over the rows a GOSS tree
keeps (``int(rows * (top_rate + other_rate))`` from the configuration's file)
at the roofline per tree, over the traced window's time per tree, idle time
and the draw included; in percent.  None where the configuration does not
sample."""

from chipbench import roofline


def read(facts):
    k = facts.counters.get("traced_trees", 0)
    p, d = facts.config["params"], facts.config["data"]
    if facts.trace is None or k <= 0 or facts.trace.window_s <= 0 or p.get("boosting") != "goss":
        return None
    rows = int(d["rows"] * (p["top_rate"] + p["other_rate"]))
    floor = roofline.pass_floor(rows, d["features"], p["max_bin"], p["num_leaves"],
                                facts.config["hist_precision"], facts.peaks)
    return 100.0 * floor["seconds"] * k / facts.trace.window_s
