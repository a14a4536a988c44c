"""Share of the configuration's rows that the leaf kernels looped over in a
wave's pass (pass log ``kind`` 1), mean over the window's wave passes: the
program's own per-pass count (``TrainRecord`` ``passes``)."""

from chipbench.layer_metrics import tree_log


def read(facts):
    return tree_log.rows_share(facts, 1)
