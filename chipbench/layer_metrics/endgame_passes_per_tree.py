"""Full-data passes of the exact endgame per tree, mean over the window's
trees: the program's own count (``TrainRecord`` ``endgame_passes``)."""

from chipbench import program_record


def read(facts):
    return program_record.window_mean(facts, "endgame_passes")
