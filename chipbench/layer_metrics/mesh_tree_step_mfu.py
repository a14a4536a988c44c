"""``tree_step_mfu`` on a mesh: one full-data pass over ONE chip's rows (the
configuration's ``mesh.rows_per_chip``) at one chip's roofline per tree, over
the traced window's time per tree, idle time and collectives included; in
percent.  Every chip of the mesh does that pass at the same time."""

from chipbench import roofline


def read(facts):
    k = facts.counters.get("traced_trees", 0)
    rows = (facts.config.get("mesh") or {}).get("rows_per_chip")
    if facts.trace is None or k <= 0 or facts.trace.window_s <= 0 or not rows:
        return None
    p, d = facts.config["params"], facts.config["data"]
    floor = roofline.pass_floor(rows, d["features"], p["max_bin"], p["num_leaves"],
                                facts.config["hist_precision"], facts.peaks)
    return 100.0 * floor["seconds"] * k / facts.trace.window_s
