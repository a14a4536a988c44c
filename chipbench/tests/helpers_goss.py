"""A tiny ``boosting=goss`` cell for the tests, beside ``helpers.make_root``'s
one: configuration ``tiny-goss`` (learning_rate 0.5, so two unsampled trees),
mix ``tiny-steady-goss`` (driver ``train_loop_goss``), cell ``tiny-goss.train``."""

from __future__ import annotations

import json
import os

from chipbench.tests import helpers

ROWS = 6000
GOSS_METRICS = ("goss_sample_device_ms_per_tree", "sampled_row_share",
                "goss_hist_kernel_roofline", "goss_tree_step_mfu")
# at 6000 rows sigma of the kept share is 5e-3 (the fault "other rows kept at other_rate"
# reads 0.025) and rows that share their leaves tie; int4 for int8 reads 0.12-0.22 at the
# median node where int8 reads 0.006-0.008
LOOSE_GOSS = {"goss_top_violations": 0, "goss_top_share_gap": 0.05, "goss_rest_rate_gap": 0.015,
              "goss_rest_bias": 0.05, "split_gain_median_gap": 0.05}
TINY_GOSS_MIX = dict(helpers.TINY_MIX, name="tiny-steady-goss", driver="train_loop_goss",
                     warmup_trees=5, warmup_unsampled=2, followed_sampled=3)


def make_root(tmp: str, control: bool = False) -> str:
    """``control``: the configuration's control in the program's place (its
    own path at the control's parameters), under the configuration's limits."""
    root = helpers.make_root(tmp, quantized=True)
    extra = os.path.join(root, "extrabench")
    cfg = helpers.tiny_config("tiny-goss", True, rows=ROWS)
    cfg["params"].update(boosting="goss", top_rate=0.2, other_rate=0.1, learning_rate=0.5)
    if control:
        cfg["params"].update(cfg["control"]["params"])
    cfg["limits"].update(LOOSE_GOSS)
    with open(os.path.join(extra, "configs", "tiny-goss.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(extra, "workloads", "tiny-steady-goss.json"), "w") as fh:
        json.dump(TINY_GOSS_MIX, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        manifest = json.load(fh)
    manifest["configs"].append({"name": "tiny-goss", "source": "test", "reduced": [], "why": "test",
                                "file": "extrabench/configs/tiny-goss.json"})
    manifest["workloads"].append({"name": "tiny-goss.train", "config": "tiny-goss",
                                  "traffic": "tiny-steady-goss", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if "criteo-q8-goss.train" in m["workloads"]:
            m["workloads"].append("tiny-goss.train")
    with open(path, "w") as fh:
        json.dump(manifest, fh)
    return root
