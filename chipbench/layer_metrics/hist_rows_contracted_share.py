"""Share of the rows that the leaf-histogram kernels looped over, of the rows
they were handed: the program's own count (``TrainRecord``
``hist_rows_contracted``, the kernels' trip counts summed over a tree's
counted passes and over the row shards) over ``hist_passes`` times the
configuration's rows, both summed over the window's trees.  1 means every
pass contracted every row.  None where the program keeps no such count."""

from chipbench import program_record


def read(facts):
    contracted = program_record.window_mean(facts, "hist_rows_contracted")
    if contracted is None:
        return None
    passes = facts.counters["hist_passes"]
    return contracted / (sum(passes) / len(passes) * facts.config["data"]["rows"])
