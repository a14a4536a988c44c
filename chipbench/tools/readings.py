"""Readings for the limits of ``correct``, taken on the chip at the cells' own
size: per seed, in ONE process (set-up is most of a run, so the seeds, both
configurations, their controls and the planted fault share it),

* the program as each configuration states it: the numbers compared;
* each configuration's control (the nearest precision below): the same numbers;
* the fault "half of the batch left out", planted in the reference put in the
  program's place.

    python -m chipbench.tools.readings --seeds 11,12,13 --configs criteo-share-q8,criteo-share-exact \
        [--control-seeds 3] [--trees 4] [--out chiprun_out/readings.jsonl]

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

from chipbench import datagen, reference
from chipbench import manifest as mf
from chipbench.drivers import train_loop


def program_numbers(lgb, params, train_set, spec, seed, xh, n_trees, cfg, dump=None):
    """Train ``n_trees`` through ``Booster.update()`` and compare as a run does.
    Returns what :func:`chipbench.reference.compare_run` returns."""
    booster = lgb.Booster(params=dict(params, verbosity=-1), train_set=train_set)
    for _ in range(n_trees):
        booster.update()
    train_loop._force(booster)
    prob = train_loop.predict_chunks(booster, xh, 16384, n_trees)
    text = booster.model_to_string()
    scores = {b: train_loop._score_rows(booster, *spec.block_range(b))
              for b in reference.sample_blocks(spec, seed, 4)}
    del booster
    gc.collect()
    out = reference.compare_run(spec, seed, reference.Params(cfg["params"]), text, scores,
                                xh, prob, n_trees)
    if dump:
        ref = out[-1]
        os.makedirs(os.path.dirname(dump), exist_ok=True)
        with open(dump + ".model.txt", "w") as fh:
            fh.write(text)
        np.savez(dump + ".ref.npz", bias=ref["bias"], **{f"{k}{t}": v for k in ("G", "H", "count")
                                                        for t, v in enumerate(ref[k])})
    return out


def bf16_scores_control(spec, seed, trees, xh) -> dict:
    """Scores and predictions are f32 in every configuration: the reference's
    own sums in bfloat16, the step below, against its float64 sums."""
    low = reference.round_bf16
    xb = datagen.block(spec, seed, reference.sample_blocks(spec, seed, 1)[0])[0]
    return {
        "train_score_gap": float(np.max(np.abs(reference.predict_raw(trees, xb, low)
                                               - reference.predict_raw(trees, xb)))),
        "heldout_pred_gap": float(np.max(np.abs(
            reference._sigmoid(reference.predict_raw(trees, xh, low))
            - reference._sigmoid(reference.predict_raw(trees, xh))))),
    }


def main(argv=None, root=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--configs", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--trees", type=int, default=4)
    ap.add_argument("--out", default=None)
    ap.add_argument("--dump", default=None, help="directory for model texts and reference sums")
    args = ap.parse_args(argv)

    from chipbench import run as bench_run
    device = bench_run.find_device(1)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()

    root = root or mf.repo_root()
    manifest = mf.load_manifest(root)
    cfgs = [mf.load_json(os.path.join(root, mf.find_named(manifest["configs"], n, "config")["file"]))
            for n in args.configs.split(",")]
    spec = datagen.TabularSpec(cfgs[0]["data"])
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
        train_set = lgb.Dataset(X, y, params=dict(cfgs[0]["params"], verbosity=-1))
        train_set.construct()
        del X
        gc.collect()
        for cfg in cfgs:
            tag = args.dump and os.path.join(args.dump, f"{cfg['name']}.{seed}")
            numbers, trees, leaf, labels, ref = program_numbers(
                lgb, cfg["params"], train_set, spec, seed, xh, args.trees, cfg,
                dump=tag and tag + ".program")
            emit(seed=seed, config=cfg["name"], kind="program", numbers=numbers)
            if i < args.control_seeds:
                emit(seed=seed, config=cfg["name"], kind="control_bf16_scores",
                     numbers=bf16_scores_control(spec, seed, trees, xh))
            if i < args.control_seeds:
                ctl = cfg["control"]
                if ctl["kind"] == "reference_rounding":
                    low = reference.recompute(trees, leaf, labels, reference.Params(cfg["params"]),
                                              grad_round=reference.ROUNDINGS[ctl["rounding"]])
                    emit(seed=seed, config=cfg["name"], kind="control",
                         numbers=reference.compare_followed(low, ref))
                # the fault: every other row left out, the sums over the rest
                keep = np.ones(spec.rows)
                keep[1::2] = 0.0
                half = reference.recompute(trees, leaf, labels, reference.Params(cfg["params"]),
                                           keep=keep)
                emit(seed=seed, config=cfg["name"], kind="fault_half_batch",
                     numbers=reference.compare_followed(half, ref))
                del keep
            del leaf, labels, ref
            gc.collect()
            if i < args.control_seeds and cfg["control"]["kind"] == "program_params":
                params = dict(cfg["params"], **cfg["control"]["params"])
                numbers, *_ = program_numbers(lgb, params, train_set, spec, seed, xh,
                                              args.trees, cfg, dump=tag and tag + ".control")
                emit(seed=seed, config=cfg["name"], kind="control", numbers=numbers)
        del train_set
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
