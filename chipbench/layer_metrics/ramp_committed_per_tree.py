"""Splits the speculative ramp's verifying pass committed per tree, of W-1
provisional, mean over the window's trees (``TrainRecord``
``ramp_committed``): the more it commits, the fewer waves follow."""

from chipbench import program_record


def read(facts):
    return program_record.window_mean(facts, "ramp_committed")
