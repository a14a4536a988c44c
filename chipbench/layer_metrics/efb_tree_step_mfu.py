"""``tree_step_mfu`` for one-hot coded rows: one full-data pass over
``data.source_columns`` one-byte values a row at the roofline per tree
(``efb_hist_kernel_roofline``'s floor), over the traced window's time per tree,
idle time included; in percent.  None where the configuration states no
``source_columns``."""

from chipbench.layer_metrics import efb_hist_kernel_roofline


def read(facts):
    k = facts.counters.get("traced_trees", 0)
    floor = efb_hist_kernel_roofline.floor_seconds(facts)
    if facts.trace is None or k <= 0 or facts.trace.window_s <= 0 or floor is None:
        return None
    return 100.0 * floor * k / facts.trace.window_s
