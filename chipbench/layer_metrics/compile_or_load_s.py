"""Seconds of backend compilation or persistent-cache retrieval of the
training programs (``setup_seconds["compile_or_load"]``)."""

from chipbench import program_record


def read(facts):
    return program_record.setup_seconds(facts, "compile_or_load")
