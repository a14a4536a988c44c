"""A temporary checkout for the tests: the real ``chipbench`` linked in, and a
second benchmark directory that ADDS a configuration, a mix, a cell and a
per-layer metric by files and entries alone, at a size the CPU holds."""

from __future__ import annotations

import copy
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_PARAMS = {
    "objective": "binary", "num_leaves": 15, "max_bin": 63, "learning_rate": 0.1,
    "min_data_in_leaf": 5,
    # the wave grower and the Pallas kernels are the TPU routing; on the CPU
    # they have to be asked for (and run interpreted)
    "tree_grow_mode": "wave", "tpu_histogram_impl": "pallas",
}
TINY_Q8 = {"use_quantized_grad": True, "num_grad_quant_bins": 254, "quant_train_renew_leaf": True}
LOOSE_LIMITS = {"leaf_count_diff": 0, "leaf_value_gap": 1e-3, "split_gain_gap": 0.5, "split_gain_median_gap": 0.1,
                "train_score_gap": 1e-4, "heldout_pred_gap": 1e-4}


def tiny_config(name: str, quantized: bool, rows: int = 6000) -> dict:
    return {
        "name": name, "source": "test",
        "params": dict(TINY_PARAMS, **(TINY_Q8 if quantized else {})),
        "hist_precision": "int8" if quantized else "bf16_hi_lo",
        "data": {"generator": "higgs_like", "rows": rows, "features": 6, "holdout_rows": 512,
                 "label_noise": 0.5, "interaction": 0.3, "weights_seed": 3},
        "limits": dict(LOOSE_LIMITS),
        "control": ({"kind": "program_params", "params": {"num_grad_quant_bins": 14}} if quantized
                    else {"kind": "reference_rounding", "rounding": "bf16"}),
        "reduced": [],
    }


TINY_MIX = {"name": "tiny-steady", "driver": "train_loop", "warmup_trees": 2,
            "min_window_trees": 4, "auc_trees": 6, "predict_chunk_rows": 256,
            "trace_trees": 2, "score_sample_blocks": 2}

DUMMY_METRIC = '''"""A per-layer metric added by a file alone."""


def read(facts):
    return float(facts.counters["window_trees"])
'''


def make_root(tmp: str, quantized: bool = True) -> str:
    """``tmp`` becomes a checkout: BENCHMARK.json (the real one plus the added
    entries), ``chipbench`` (linked), ``extrabench`` (the added files)."""
    os.symlink(os.path.join(REPO, "chipbench"), os.path.join(tmp, "chipbench"))
    extra = os.path.join(tmp, "extrabench")
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(os.path.join(extra, d))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    manifest = copy.deepcopy(manifest)
    manifest["paths"].append("extrabench")
    cfg = tiny_config("tiny", quantized)
    with open(os.path.join(extra, "configs", "tiny.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(extra, "workloads", "tiny-steady.json"), "w") as fh:
        json.dump(TINY_MIX, fh)
    with open(os.path.join(extra, "layer_metrics", "trees_in_window.py"), "w") as fh:
        fh.write(DUMMY_METRIC)
    manifest["configs"].append({"name": "tiny", "source": "test", "file": "extrabench/configs/tiny.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny.train", "config": "tiny", "traffic": "tiny-steady",
                                  "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "trees_in_window", "unit": "trees", "better": "higher",
                                  "source": "program_counter", "layer": "grower",
                                  "moves": "train_iters_per_s", "workloads": ["tiny.train"]})
    for m in manifest["per_layer"]:
        if m["name"] != "trees_in_window":
            m["workloads"] = m["workloads"] + ["tiny.train"]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as fh:
        json.dump(manifest, fh)
    return tmp


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
