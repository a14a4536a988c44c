"""Operand bytes of the one histogram collective a full wave pass makes: the
local histogram batch every chip hands to the merge, as the program's
``TrainRecord`` tallied it while tracing (``snapshot()["collectives"]``, the
largest single operand at the data-parallel histogram site).  A number that
repeats exactly and moves only when someone changes the payload.  None where
the program traced no such site."""

HIST_SITES = ("data_parallel/wave/hist_reduce_scatter", "data_parallel/wave/hist_psum")


def read(facts):
    table = facts.counters.get("collectives") or {}
    sizes = [table[site].get("max_operand_bytes", 0) for site in HIST_SITES if site in table]
    return max(sizes) if sizes and max(sizes) > 0 else None
