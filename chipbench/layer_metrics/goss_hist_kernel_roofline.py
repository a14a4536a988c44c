"""``hist_kernel_roofline`` for a sampled tree: the least time one pass needs
over the rows a GOSS tree keeps, ``int(rows * (top_rate + other_rate))`` from
the configuration's file (the work a sampled pass needs whatever implements
it: an out-of-bag row adds to no histogram), over the measured histogram
kernel time per pass; in percent.  None where the configuration does not
sample."""

from chipbench import roofline


def read(facts):
    secs, passes = facts.traced_kernel_s("lgbm_hist_"), facts.traced_passes()
    p, d = facts.config["params"], facts.config["data"]
    if secs is None or passes is None or p.get("boosting") != "goss":
        return None
    rows = int(d["rows"] * (p["top_rate"] + p["other_rate"]))
    floor = roofline.pass_floor(rows, d["features"], p["max_bin"], p["num_leaves"],
                                facts.config["hist_precision"], facts.peaks)
    return 100.0 * floor["seconds"] / (secs / passes)
