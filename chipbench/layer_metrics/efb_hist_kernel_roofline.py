"""``hist_kernel_roofline`` for one-hot coded rows: the least time one
full-data pass needs over the values a row really carries,
``data.source_columns`` one-byte values (a row's 4,213 indicator columns hold
one set value a coded source column: a floor over ``data.features`` columns
would read far over 100% against a kernel that streams some 50 bundle
columns), over the measured histogram kernel time per pass; in percent.  None
where the configuration states no ``source_columns``."""

from chipbench import roofline


def floor_seconds(facts):
    p, d = facts.config["params"], facts.config["data"]
    if "source_columns" not in d:
        return None
    return roofline.pass_floor(d["rows"], d["source_columns"], p["max_bin"], p["num_leaves"],
                               facts.config["hist_precision"], facts.peaks)["seconds"]


def read(facts):
    secs, passes, floor = facts.traced_kernel_s("lgbm_hist_"), facts.traced_passes(), \
        floor_seconds(facts)
    if secs is None or passes is None or floor is None:
        return None
    return 100.0 * floor / (secs / passes)
