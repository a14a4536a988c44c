"""Readings for the limits of an integer + categorical configuration's
``correct``, taken on the chip at the cell's own size: per seed, in ONE process,

* the program as the configuration states it: the eight numbers compared;
* on the first ``--control-seeds`` seeds, the four planted faults of
  ``chipbench.reference_cat.FAULTS`` on the same run's answers (a missing,
  folded or unseen category walked as the column's most frequent one; every
  categorical node stating its best one-vs-rest gain; every left set cut to
  its first 4 categories; the NaN direction of the numerical nodes flipped),
  and the configuration's control (the program's own path at the control's
  parameters).

    python -m chipbench.tools.readings_cat --seeds 11,12 --config criteo-kaggle-cat-q8 \
        [--control-seeds 1] [--trees 6] [--out chiprun_out/readings_cat.jsonl]

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

from chipbench import datagen_ctr, reference, reference_cat
from chipbench import manifest as mf
from chipbench.drivers import train_loop


def answers(lgb, params, train_set, spec, seed, xh, n_trees):
    """Train ``n_trees`` through ``Booster.update()``; the model text, the
    final training scores on the sampled blocks and the held-out predictions."""
    booster = lgb.Booster(params=dict(params, verbosity=-1), train_set=train_set)
    for _ in range(n_trees):
        booster.update()
    train_loop._force(booster)
    prob = train_loop.predict_chunks(booster, xh, 16384, n_trees)
    text = booster.model_to_string()
    scores = {b: train_loop._score_rows(booster, *spec.block_range(b))
              for b in reference.sample_blocks(spec, seed, 4)}
    grower = booster.train_record.snapshot().get("grower", {})
    del booster
    gc.collect()
    return text, scores, prob, grower


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
    spec = datagen_ctr.CtrSpec(cfg["data"])
    rp = reference_cat.Params(cfg["params"])
    tables = datagen_ctr.Tables(spec)
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
        blocks, y = datagen_ctr.training_blocks(spec, seed, tables)
        xh, _ = datagen_ctr.holdout(spec, seed, tables)
        params = dict(cfg["params"], verbosity=-1)
        train_set = lgb.Dataset(blocks, y, params=params,
                                categorical_feature=spec.categorical_feature)
        train_set.construct()
        del blocks
        gc.collect()
        given = answers(lgb, cfg["params"], train_set, spec, seed, xh, args.trees)
        text, scores, prob, grower = given
        numbers, trees, _, ref = reference_cat.compare_run(
            spec, seed, rp, text, scores, xh, prob, args.trees)
        cat, nodes = reference_cat.cat_split_counts(trees)
        emit(seed=seed, config=cfg["name"], kind="program", numbers=numbers, grower=grower,
             cat_splits=cat, internal_nodes=nodes)
        if i < args.control_seeds:
            # one-vs-rest only needs no second walk: the sound run's searches hold it
            emit(seed=seed, config=cfg["name"], kind="fault_onehot_only",
                 numbers=dict(numbers, cat_search_gap=reference_cat.cat_search_gap(
                     trees, ref["search"], stated_gain=lambda t, node, best_oh: best_oh)))
            for fault in ("unknown_as_top", "cut_left_sets", "flip_nan"):
                faulted, *_ = reference_cat.compare_run(
                    spec, seed, rp, text, scores, xh, prob, args.trees, fault=fault)
                emit(seed=seed, config=cfg["name"], kind="fault_" + fault, numbers=faulted)
            if cfg["control"]["kind"] == "program_params":
                text, scores, prob, _ = answers(
                    lgb, dict(cfg["params"], **cfg["control"]["params"]), train_set, spec,
                    seed, xh, args.trees)
                numbers, *_ = reference_cat.compare_run(
                    spec, seed, rp, text, scores, xh, prob, args.trees)
                emit(seed=seed, config=cfg["name"], kind="control", numbers=numbers)
        del train_set
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
