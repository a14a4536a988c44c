"""Share of the configuration's rows that the leaf kernels looped over in a
pass of the exact endgame (pass log ``kind`` 2), mean over the window's
endgame passes: the program's own per-pass count (``TrainRecord``
``passes``).  None where the window holds no endgame pass."""

from chipbench.layer_metrics import tree_log


def read(facts):
    return tree_log.rows_share(facts, 2)
