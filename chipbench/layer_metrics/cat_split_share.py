"""Share of the window's internal nodes that are categorical splits: the
program's own count (``TrainRecord`` ``cat_splits``, read off the node records
the grower returns) over ``num_leaves - 1``, both summed over the window's
trees.  None where the program keeps no such count (the parent of the PR
that added it)."""

from chipbench import program_record


def read(facts):
    cat = program_record.window_mean(facts, "cat_splits")
    leaves = program_record.window_mean(facts, "num_leaves")
    if cat is None or leaves is None or leaves <= 1:
        return None
    return cat / (leaves - 1)
