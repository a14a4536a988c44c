"""A tiny integer + categorical cell for the tests, beside
``helpers.make_root``'s one: configuration ``tiny-cat`` (generator
``criteo_raw_like``: 3 count columns and 4 categorical ones with missing
values), mix ``tiny-steady-cat`` (driver ``train_loop_cat``), cell
``tiny-cat.train``."""

from __future__ import annotations

import json
import os

from chipbench.tests import helpers

ROWS = 6000
CAT_METRICS = ("cat_split_share", "cat_scan_device_ms_per_tree", "row_update_kernel_roofline")
TINY_DATA = {
    "generator": "criteo_raw_like", "rows": ROWS, "features": 7, "integer_columns": 3,
    "categorical_columns": 4, "holdout_rows": 512, "weights_seed": 5,
    "max_ids": 64, "zipf_exponent": 1.1,
    "cardinality": [3, 12, 40, 5000],
    "cat_missing": [0.0, 0.05, 0.4, 0.0],
    "int_missing": [0.3, 0.0, 0.6],
    "int_log_mean": [0.5, 2.0, -0.5], "int_log_sigma": [1.2, 1.5, 0.8],
    "int_center": [0.9, 2.1, 0.2], "int_spread": [0.86, 1.3, 0.4],
    "int_weight": 2.0, "cat_weight": 1.0,
    "interaction": {"integer_column": 1, "categorical_column": 1, "weight": 0.4},
    "intercept": -0.8,
}
# at 6000 rows int8 levels read 6e-4 at the median node where int4 reads 1e-2 (no ramp:
# no gain is a subsample's estimate); a node's best subset differs from the program's
# by its group rule at these counts (6e-2), one-vs-rest alone reads over 0.5
LOOSE_CAT = {"cat_law_violations": 0, "cat_search_gap": 0.3, "split_gain_median_gap": 4e-3}
TINY_CAT_MIX = dict(helpers.TINY_MIX, name="tiny-steady-cat", driver="train_loop_cat")


def tiny_cat_config(control: bool = False) -> dict:
    cfg = helpers.tiny_config("tiny-cat", True, rows=ROWS)
    cfg["data"] = dict(TINY_DATA)
    cfg["params"].update(categorical_feature=[3, 4, 5, 6], max_cat_threshold=32, cat_l2=10.0,
                         cat_smooth=10.0, max_cat_to_onehot=4, min_data_per_group=20,
                         use_missing=True, min_data_in_leaf=20)
    if control:
        cfg["params"].update(cfg["control"]["params"])
    cfg["limits"].update(LOOSE_CAT)
    return cfg


def make_root(tmp: str, control: bool = False) -> str:
    """``control``: the configuration's control in the program's place (its
    own path at the control's parameters), under the configuration's limits."""
    root = helpers.make_root(tmp, quantized=True)
    extra = os.path.join(root, "extrabench")
    with open(os.path.join(extra, "configs", "tiny-cat.json"), "w") as fh:
        json.dump(tiny_cat_config(control), fh)
    with open(os.path.join(extra, "workloads", "tiny-steady-cat.json"), "w") as fh:
        json.dump(TINY_CAT_MIX, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({"name": "tiny-cat", "source": "test", "reduced": [], "why": "test",
                                "file": "extrabench/configs/tiny-cat.json"})
    manifest["workloads"].append({"name": "tiny-cat.train", "config": "tiny-cat",
                                  "traffic": "tiny-steady-cat", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if "criteo-cat-q8.train" in m["workloads"]:
            m["workloads"].append("tiny-cat.train")
    with open(path, "w") as fh:
        json.dump(manifest, fh)
    return root
