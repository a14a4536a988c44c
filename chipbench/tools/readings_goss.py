"""Readings for the limits of a ``boosting=goss`` configuration's ``correct``,
taken on the chip at the cell's own size: per seed, in ONE process,

* the program as the configuration states it: the ten numbers compared;
* on the first ``--control-seeds`` seeds, the planted faults, applied to the
  same run's answers along the same walk (the reference told that the
  amplification is 1; the classes' other rows redrawn at ``other_rate``
  instead of ``other_rate / (1 - top_rate)``; the top set cut at the k-th
  largest of every 64th row; the other rows redrawn in proportion to their
  ``|g*h|``), and the configuration's control (the program's own path at the
  control's parameters).

    python -m chipbench.tools.readings_goss --seeds 11,12 --config criteo-share-q8-goss \
        [--control-seeds 1] [--sampled 3] [--out chiprun_out/readings_goss.jsonl]

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

import numpy as np

from chipbench import datagen, reference, reference_goss
from chipbench import manifest as mf
from chipbench.drivers import train_loop


def answers(lgb, params, train_set, spec, seed, xh, unsampled, sampled):
    """Train ``unsampled + sampled`` trees through ``Booster.update()``; the
    model text, the sampled trees' classes, the final training scores on the
    sampled blocks and the held-out predictions."""
    booster = lgb.Booster(params=dict(params, verbosity=-1), train_set=train_set)
    classes = []
    for i in range(unsampled + sampled):
        booster.update()
        if i >= unsampled:
            classes.append(booster._gbdt.last_sample())
    train_loop._force(booster)
    classes = [np.asarray(c) for c in classes]
    n_trees = unsampled + sampled
    prob = train_loop.predict_chunks(booster, xh, 16384, n_trees)
    text = booster.model_to_string()
    scores = {b: train_loop._score_rows(booster, *spec.block_range(b))
              for b in reference.sample_blocks(spec, seed, 4)}
    del booster
    gc.collect()
    return text, classes, scores, prob, n_trees


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--sampled", type=int, default=3)
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
    spec = datagen.TabularSpec(cfg["data"])
    rp = reference_goss.Params(cfg["params"])
    unsampled = rp.unsampled_trees
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
        X, y = datagen.training_matrix(spec, seed)
        xh, _ = datagen.holdout(spec, seed)
        train_set = lgb.Dataset(X, y, params=dict(cfg["params"], verbosity=-1))
        train_set.construct()
        del X
        gc.collect()
        text, classes, scores, prob, n_trees = answers(
            lgb, cfg["params"], train_set, spec, seed, xh, unsampled, args.sampled)
        numbers, trees, leaf, labels = reference_goss.compare_run(
            spec, seed, rp, text, classes, unsampled, scores, xh, prob, n_trees)
        emit(seed=seed, config=cfg["name"], kind="program", numbers=numbers)
        if i < args.control_seeds:
            follow = lambda **fault: reference_goss.follow_sampled(
                trees, leaf, labels, rp, classes, unsampled, **fault)
            emit(seed=seed, config=cfg["name"], kind="fault_amplification_1",
                 numbers=follow(amplification=1.0))
            emit(seed=seed, config=cfg["name"], kind="fault_rest_at_other_rate",
                 numbers=follow(alter=reference_goss.redraw_rest(rp.other_rate, seed)))
            emit(seed=seed, config=cfg["name"], kind="fault_subsample_threshold",
                 numbers=follow(alter=reference_goss.subsample_threshold(rp)))
            emit(seed=seed, config=cfg["name"], kind="fault_draw_favours_large",
                 numbers=follow(alter=reference_goss.favour_large(rp.rest_rate, seed)))
        del leaf, labels, classes
        gc.collect()
        if i < args.control_seeds and cfg["control"]["kind"] == "program_params":
            params = dict(cfg["params"], **cfg["control"]["params"])
            text, classes, scores, prob, n_trees = answers(
                lgb, params, train_set, spec, seed, xh, unsampled, args.sampled)
            numbers, *_ = reference_goss.compare_run(
                spec, seed, rp, text, classes, unsampled, scores, xh, prob, n_trees)
            emit(seed=seed, config=cfg["name"], kind="control", numbers=numbers)
            del classes
        del train_set
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
