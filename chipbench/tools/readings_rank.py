"""Readings for the limits of a lambdarank configuration's ``correct``, taken
on the chip at the cell's own size: per seed, in ONE process, on one
``lgb.Dataset`` of the seed's query-aligned blocks,

* the program as the configuration states it: the seven numbers compared;
* on the first ``--control-seeds`` seeds, the configuration's control (the
  program's own path at the control's parameters: truncation 29) and one
  planted fault (the program with ``lambdarank_norm`` off), each judged
  against the reference at the configuration's own parameters.

    python -m chipbench.tools.readings_rank --seeds 11,12 --config istella-letor-rank-q8 \
        [--control-seeds 1] [--trees 6] [--kinds program,control,fault_norm_dropped] \
        [--out chiprun_out/readings_rank.jsonl]

``--kinds`` names the readings taken (all three by default).

One JSON line per reading on standard output (and appended to ``--out``).
The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from chipbench import datagen_rank, reference_rank
from chipbench import manifest as mf
from chipbench.drivers import train_loop
from chipbench.drivers.train_loop_rank import read_sample

SAMPLED_QUERIES = 64
FAULTS = {"norm_dropped": {"lambdarank_norm": False}}


def answers(lgb, params, train_set, spec, seed, xh, n_trees, truncation):
    """Train ``n_trees`` through ``Booster.update()``; the model text, what it
    gives on the sampled queries, the held-out raw scores."""
    booster = lgb.Booster(params=dict(params, verbosity=-1), train_set=train_set)
    for _ in range(n_trees):
        booster.update()
    train_loop._force(booster)
    pred = train_loop.predict_chunks(booster, xh, 16384, n_trees)
    text = booster.model_to_string()
    sample = read_sample(booster, spec, seed, SAMPLED_QUERIES, truncation)
    del booster
    gc.collect()
    return text, sample, pred


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--trees", type=int, default=6)
    ap.add_argument("--kinds", default="program,control," + ",".join(
        "fault_" + name for name in FAULTS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import run as bench_run
    device = bench_run.find_device(1)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()

    root = root or mf.repo_root()
    manifest = mf.load_manifest(root)
    cfg = mf.load_json(os.path.join(root, mf.find_named(manifest["configs"], args.config,
                                                       "config")["file"]))
    spec = datagen_rank.RankSpec(cfg["data"])
    rp = reference_rank.Params(cfg["params"])
    t0 = time.perf_counter()

    def emit(**rec):
        rec["t"] = round(time.perf_counter() - t0, 1)
        rec["device"] = device["kind"]
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(line + "\n")

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        blocks, y = datagen_rank.training_blocks(spec, seed)
        xh, yh, lengths_h = datagen_rank.holdout(spec, seed)
        params = dict(cfg["params"], verbosity=-1)
        train_set = lgb.Dataset(blocks, y, group=spec.lengths, params=params)
        train_set.construct()
        del blocks
        gc.collect()
        variants = [("program", {})]
        if i < args.control_seeds:
            if cfg["control"]["kind"] == "program_params":
                variants.append(("control", cfg["control"]["params"]))
            variants += [("fault_" + name, p) for name, p in FAULTS.items()]
        for kind, extra in variants:
            if kind not in args.kinds.split(","):
                continue
            text, sample, pred = answers(lgb, dict(cfg["params"], **extra), train_set, spec,
                                         seed, xh, args.trees, rp.truncation)
            numbers, _ = reference_rank.compare_run(spec, seed, rp, text, sample, xh, pred,
                                                    args.trees)
            emit(seed=seed, config=cfg["name"], kind=kind, numbers=numbers,
                 heldout_auc=reference_rank.query_auc(yh, pred, lengths_h))
        del train_set
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
