"""``hist_kernel_roofline`` on a mesh: the least time one full-data pass needs
over ONE chip's rows (the configuration's ``mesh.rows_per_chip``, against one
chip's peaks) over the measured histogram kernel time per pass, which is
already a mean over the devices; in percent."""

from chipbench import roofline


def read(facts):
    secs, passes = facts.traced_kernel_s("lgbm_hist_"), facts.traced_passes()
    rows = (facts.config.get("mesh") or {}).get("rows_per_chip")
    if secs is None or passes is None or not rows:
        return None
    p, d = facts.config["params"], facts.config["data"]
    floor = roofline.pass_floor(rows, d["features"], p["max_bin"], p["num_leaves"],
                                facts.config["hist_precision"], facts.peaks)
    return 100.0 * floor["seconds"] / (secs / passes)
