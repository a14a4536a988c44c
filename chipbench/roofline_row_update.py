"""What routing one wave's rows needs, whatever implements it, and the least
time the chip could take for it.

After a wave's splits are chosen every row's leaf id is read and written
(a row of a split leaf moves to a child), its channel for the wave's
histogram pass is written, and ONE bin code is read: the code of the feature
its own leaf splits on.  No wave width, table size or column copy enters
(the kernel as written reads W codes a row), so a later kernel cannot make
the count stale.  The routing does no arithmetic worth a peak: HBM bytes
bound it.
"""

from __future__ import annotations

from .roofline import bin_bytes, leaf_id_bytes

CHANNEL_BYTES = 1       # a row's wave channel: -1 or one of at most 42 slots


def row_update_bytes(rows: int, max_bin: int, num_leaves: int) -> int:
    """Bytes one wave's routing moves at the least: per row the leaf id in
    and out, the channel out, one bin code in."""
    return rows * (2 * leaf_id_bytes(num_leaves) + CHANNEL_BYTES + bin_bytes(max_bin))


def row_update_floor(rows: int, max_bin: int, num_leaves: int, peaks: dict) -> float:
    """Least seconds for one wave's routing."""
    return row_update_bytes(rows, max_bin, num_leaves) / peaks["hbm_bytes_per_s"]
