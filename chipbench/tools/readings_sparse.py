"""Readings for the limits of a sparse one-hot configuration's ``correct``,
taken on the chip at the cell's own size: per seed, in ONE process,

* the program as the configuration states it: the eight numbers compared;
* on the first ``--control-seeds`` seeds, the four planted faults of
  ``chipbench.reference_sparse.FAULTS`` on the same run's answers (a bundle
  member's default bin left empty; two members' places swapped; a bundle's
  conflict rows routed by the losing member; a scan that skips every second
  bundle), and the configuration's control (the program's own path at the
  control's parameters).

    python -m chipbench.tools.readings_sparse --seeds 11,12 --config allstate-onehot-efb-q8 \
        [--control-seeds 1] [--trees 6] [--out chiprun_out/readings_sparse.jsonl]

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

from chipbench import datagen_sparse, reference, reference_sparse
from chipbench import manifest as mf
from chipbench.drivers import train_loop


def answers(lgb, params, train_set, spec, seed, xh, n_trees):
    """Train ``n_trees`` through ``Booster.update()``; the model text, the
    final training scores on the sampled blocks, the held-out predictions and
    the record's statement of the bundling and the grower's paths."""
    booster = lgb.Booster(params=dict(params, verbosity=-1), train_set=train_set)
    for _ in range(n_trees):
        booster.update()
    train_loop._force(booster)
    prob = train_loop.predict_chunks(booster, datagen_sparse.Rows(xh), 16384, n_trees)
    text = booster.model_to_string()
    scores = {b: train_loop._score_rows(booster, *spec.block_range(b))
              for b in reference.sample_blocks(spec, seed, 4)}
    snap = booster.train_record.snapshot()
    del booster
    gc.collect()
    return text, scores, prob, {"grower": snap.get("grower", {}), "efb": snap.get("efb", {})}


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--trees", type=int, default=6)
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
    spec = datagen_sparse.SparseSpec(cfg["data"])
    rp = reference_sparse.Params(cfg["params"])
    tables = datagen_sparse.Tables(spec)
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
        x, y = datagen_sparse.training_matrix(spec, seed, tables)
        xh, yh = datagen_sparse.holdout(spec, seed, tables)
        params = dict(cfg["params"], verbosity=-1)
        train_set = lgb.Dataset(x, y, params=params)
        train_set.construct()
        del x
        gc.collect()
        conflicts = reference_sparse.Conflicts(*train_set.efb_conflicts(),
                                               train_set.efb.record()["conflict_rows"])
        text, scores, prob, stated = answers(lgb, cfg["params"], train_set, spec, seed, xh,
                                             args.trees)
        numbers, trees, _ = reference_sparse.compare_run(
            spec, seed, rp, text, scores, xh, prob, args.trees, conflicts)
        emit(seed=seed, config=cfg["name"], kind="program", numbers=numbers, **stated,
             heldout_auc=reference.auc(yh, prob),
             indicator_splits=sum(reference_sparse.indicator_split_counts(spec, trees)),
             internal_nodes=sum(t.num_leaves - 1 for t in trees))
        if i < args.control_seeds:
            for fault in reference_sparse.FAULTS:
                faulted, *_ = reference_sparse.compare_run(
                    spec, seed, rp, text, scores, xh, prob, args.trees, conflicts, fault=fault)
                emit(seed=seed, config=cfg["name"], kind="fault_" + fault, numbers=faulted)
            if cfg["control"]["kind"] == "program_params":
                text, scores, prob, _ = answers(
                    lgb, dict(cfg["params"], **cfg["control"]["params"]), train_set, spec,
                    seed, xh, args.trees)
                numbers, *_ = reference_sparse.compare_run(
                    spec, seed, rp, text, scores, xh, prob, args.trees, conflicts)
                emit(seed=seed, config=cfg["name"], kind="control", numbers=numbers)
        del train_set
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
