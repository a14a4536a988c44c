"""Host seconds of ``Dataset.construct``'s row sample and per-feature
``find_bin`` (the program's ``setup_seconds["bin_find"]``)."""

from chipbench import program_record


def read(facts):
    return program_record.setup_seconds(facts, "bin_find")
