"""What one full-data histogram pass needs, whatever implements it, and the
least time the chip could take for it.

The count uses the cell's shapes and stated precision only: every bin code is
read once, every row's gradient pair and leaf id are read once, and each
(row, feature) adds three channels (gradient, hessian, count) into its bin.
No tile, wave or one-hot size enters, so a later kernel cannot make the count
stale.  Peaks come from ``peaks.json`` by ``device_kind``; a device that is
not in the table is an error, not a default.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

# bytes of one row's gradient pair, and the peak its adds run against
PRECISIONS = {
    "int8": {"grad_pair_bytes": 2, "peak": "int8_ops_per_s"},
    "bf16_hi_lo": {"grad_pair_bytes": 8, "peak": "bf16_flops_per_s"},
}
CHANNELS = 3            # gradient, hessian, count


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    with open(path or os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def bin_bytes(max_bin: int) -> int:
    return 1 if max_bin <= 256 else 2


def leaf_id_bytes(num_leaves: int) -> int:
    return 1 if num_leaves <= 256 else 2


def pass_bytes(rows: int, features: int, max_bin: int, num_leaves: int, precision: str) -> int:
    per_row = PRECISIONS[precision]["grad_pair_bytes"] + leaf_id_bytes(num_leaves)
    return rows * features * bin_bytes(max_bin) + rows * per_row


def pass_ops(rows: int, features: int) -> int:
    return rows * features * CHANNELS


def pass_floor(rows: int, features: int, max_bin: int, num_leaves: int,
               precision: str, peaks: dict) -> dict:
    """Least seconds for one pass, and which peak bounds it."""
    t_bytes = pass_bytes(rows, features, max_bin, num_leaves, precision) / peaks["hbm_bytes_per_s"]
    t_ops = pass_ops(rows, features) / peaks[PRECISIONS[precision]["peak"]]
    return {"seconds": max(t_bytes, t_ops),
            "bound_by": "hbm_bytes" if t_bytes >= t_ops else PRECISIONS[precision]["peak"]}
