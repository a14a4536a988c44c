"""Host seconds of ``Dataset.construct``'s column selection and bin mapping,
the native call's contiguous copy included (``setup_seconds["bin_matrix"]``)."""

from chipbench import program_record


def read(facts):
    return program_record.setup_seconds(facts, "bin_matrix")
