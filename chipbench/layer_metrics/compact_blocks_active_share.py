"""Share of the row compaction's blocks that held at least one active lane,
over the window's compacted passes (``TrainRecord`` ``passes``:
``blocks_active`` over ``blocks``): what skipping the blocks that hold none
could save.  The compacted passes are the waves' and the endgame's and, in
a grower built for a booster that samples rows (``snapshot()["grower"]``
``sampled``), the first pass too."""

from chipbench import program_record
from chipbench.layer_metrics import tree_log


def read(facts):
    grower = (program_record.snapshot(facts) or {}).get("grower", {})
    passes = tree_log.passes_of(facts, (0, 1, 2) if grower.get("sampled") else (1, 2))
    blocks = sum(p["blocks"] for p in passes or ())
    return sum(p["blocks_active"] for p in passes) / blocks if blocks else None
