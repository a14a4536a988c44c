"""What one lambdarank gradient step needs, whatever implements it, and the
least time the chip could take for it.

The count uses the cell's shapes only: every row's score and label are read
once and its lambda and hessian written once (float32 each), and every
query's inverse max DCG is read once.  No bucket, pair block, sort or chunk
enters, so a later layout cannot make the count stale.  The pair arithmetic
runs on the vector units, and ``peaks.json`` has no published VPU peak for
the chip, so this floor counts HBM bytes alone: it is a floor of the
gradient step's bytes, not of its operations, and a share of it says how far
the step is from streaming its inputs and outputs once.
"""

from __future__ import annotations

FLOAT_BYTES = 4
ROW_ARRAYS = 4          # score and label in, lambda and hessian out


def gradient_bytes(rows: int, queries: int) -> int:
    """Bytes one gradient step moves at the least."""
    return rows * ROW_ARRAYS * FLOAT_BYTES + queries * FLOAT_BYTES


def gradient_floor(rows: int, queries: int, peaks: dict) -> float:
    """Least seconds for one gradient step."""
    return gradient_bytes(rows, queries) / peaks["hbm_bytes_per_s"]
