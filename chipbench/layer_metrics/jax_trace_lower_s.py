"""Seconds JAX spent tracing the training programs to jaxprs and lowering
them to MLIR in this process (``setup_seconds["jax_trace_lower"]``): paid by
every process, whatever the persistent cache holds."""

from chipbench import program_record


def read(facts):
    return program_record.setup_seconds(facts, "jax_trace_lower")
