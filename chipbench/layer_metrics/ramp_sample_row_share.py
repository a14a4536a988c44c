"""Share of the speculative ramp's subsample that is in the tree's bag:
``TrainRecord`` ``ramp_sample_rows`` over ``ramp_sample_lanes``, both summed
over the window's trees.  1 where nothing samples rows; None where the ramp
is off."""

from chipbench.layer_metrics import tree_log


def read(facts):
    rows = tree_log.window_rows(facts)
    if rows is None or any("ramp_sample_lanes" not in r for r in rows):
        return None
    lanes = sum(r["ramp_sample_lanes"] for r in rows)
    return sum(r["ramp_sample_rows"] for r in rows) / lanes if lanes else None
