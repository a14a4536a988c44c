"""Driver ``train_loop_goss``: ``train_loop``'s steady-state boosting with
``boosting=goss``, warmed up past the trees that GOSS does not sample.

GOSS sees every row for its first ``1/learning_rate`` trees.  So the warm-up
grows the mix's ``warmup_unsampled`` trees and then ``followed_sampled`` more,
the first sampled ones, which compiles the sampler before the window opens:
every tree of the window, and each traced one, is a sampled tree.  After each
followed tree the program's per-row classes of its draw are taken with
``GBDT.last_sample()`` as device arrays, copied to the host after the
warm-up's force and freed.  The window, the counters and the answers read
back are ``train_loop``'s; ``chipbench.reference_goss`` decides ``correct``:
it holds the three draws to the sample's law and the three sampled trees to
their weighted sums.

Before any data is made a 64-row ``goss`` booster is built: a program without
``last_sample()`` ends there, in seconds, with an error.  After the window the
run ends where a window tree's ``sampled_rows / N`` (the program's own
counter) lies outside ``top_rate + other_rate`` +- ``SHARE_TOLERANCE``: the
``goss_*`` readers divide by that share's rows.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from chipbench import datagen, reference, reference_goss, roofline, trace_reduce
from chipbench.drivers import train_loop as tl
from chipbench.drivers.train_loop import CompileCounter, predict_chunks, window_loop
from chipbench.facts import Facts

SHARE_TOLERANCE = 0.01


def require_last_sample(lgb, params: dict) -> None:
    """A 64-row ``goss`` booster, not trained; SystemExit where the program
    cannot say which rows a tree sampled."""
    x = np.arange(128, dtype=np.float32).reshape(64, 2)
    probe = dict(objective="binary", boosting="goss", top_rate=params["top_rate"],
                 other_rate=params["other_rate"], min_data_in_bin=1, min_data_in_leaf=1,
                 verbosity=-1)
    try:
        booster = lgb.Booster(params=probe, train_set=lgb.Dataset(x, (x[:, 0] > 64).astype(
            np.float32), params=probe))
        reader = getattr(booster._gbdt, "last_sample", None)
    except Exception as exc:
        raise SystemExit(f"this program builds no boosting=goss booster "
                         f"({type(exc).__name__}: {exc}). Not running.")
    if not callable(reader):
        raise SystemExit("this program's GBDT has no last_sample(): it cannot say which rows a "
                         "sampled tree kept, and the cell's reference checks exactly that. "
                         "Not running.")


def require_sampled(sampled_rows: list, rows: int, share: float) -> None:
    """Every window tree's bag against the configuration's share: an
    unsampled tree in the window (or a sampler that keeps another share)
    would be timed, and read by the ``goss_*`` metrics, as what it is not."""
    off = [(i, n) for i, n in enumerate(sampled_rows)
           if not abs(n / rows - share) <= SHARE_TOLERANCE]
    if off:
        raise SystemExit(f"window trees (index, rows in the bag) {off[:5]} lie outside "
                         f"{share} +- {SHARE_TOLERANCE} of {rows} rows. Not a goss window.")


def run(run) -> dict:
    cfg, mix, log = run.config, run.mix, run.log
    spec = datagen.TabularSpec(cfg["data"])
    params = dict(cfg["params"], verbosity=-1)
    ref_params = reference_goss.Params(cfg["params"])
    peaks = roofline.load_peaks(run.device["kind"])
    unsampled, followed = int(mix["warmup_unsampled"]), int(mix["followed_sampled"])
    warm = int(mix["warmup_trees"])
    if warm != unsampled + followed or unsampled != ref_params.unsampled_trees:
        raise SystemExit(f"mix {mix['name']}: warmup_trees {warm} must be warmup_unsampled "
                         f"{unsampled} (1/learning_rate = {ref_params.unsampled_trees}) "
                         f"+ followed_sampled {followed}")

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {configure_compile_cache()}; host cores {os.cpu_count()}, "
        f"free memory {tl._free_host_gb():.1f} GB")
    require_last_sample(lgb, cfg["params"])

    # ---- set-up --------------------------------------------------------
    compiles = CompileCounter()
    t = time.perf_counter()
    X, y = datagen.training_matrix(spec, run.seed)
    xh, yh = datagen.holdout(spec, run.seed)
    generate_s = time.perf_counter() - t
    log(f"data: {spec.rows}+{spec.holdout_rows} x {spec.features} made in {generate_s:.1f}s")
    t = time.perf_counter()
    train_set = lgb.Dataset(X, y, params=params)
    train_set.construct()
    binning_s = time.perf_counter() - t
    del X
    log(f"binning: {binning_s:.1f}s")
    t = time.perf_counter()
    booster = lgb.Booster(params=params, train_set=train_set)
    classes = []
    for i in range(warm):
        booster.update()
        drawn = booster._gbdt.last_sample()          # a device array: nothing waits here
        if (drawn is not None) != (i >= unsampled):
            raise SystemExit(f"warm-up tree {i + 1} was {'' if drawn is not None else 'not '}"
                             f"sampled; the mix states {unsampled} unsampled trees")
        if drawn is not None:
            classes.append(drawn)
    tl._force(booster)
    classes = [np.asarray(c) for c in classes]       # 1 byte a row each; the device's are freed
    warmup_s = time.perf_counter() - t
    log(f"upload, compile or cache load, {unsampled}+{followed} warm-up trees, "
        f"{followed} draws copied: {warmup_s:.1f}s")

    # ---- window --------------------------------------------------------
    trace_dir = os.path.join(run.root, ".chipbench_trace", run.cell["name"])
    span = jax.profiler.TraceAnnotation

    def update():
        with span(trace_reduce.SPAN_PREFIX + "update"):
            booster.update()

    def force():
        with span(trace_reduce.SPAN_PREFIX + "force"):
            tl._force(booster)

    def traced_trees(update_fn) -> int:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with span(trace_reduce.WINDOW_SPAN):
                for _ in range(int(mix["trace_trees"])):
                    update_fn()
                force()
        finally:
            jax.profiler.stop_trace()
        return int(mix["trace_trees"])

    compiles_before = compiles.count
    setup_s = time.perf_counter() - run.t0
    win = window_loop(update, force, run.seconds, int(mix["min_window_trees"]),
                      first_trees=traced_trees if run.trace else None)
    memory_peak = tl._memory_peak()
    compiles_in_window = compiles.count - compiles_before
    log(f"window: {win['trees']} trees in {win['seconds']:.2f}s; "
        f"compiles in window {compiles_in_window}; peak {memory_peak / 1e9:.2f} GB")

    # ---- the program's answers ----------------------------------------
    auc_trees = int(mix["auc_trees"])
    with span(trace_reduce.SPAN_PREFIX + "predict"):
        prob = predict_chunks(booster, xh, int(mix["predict_chunk_rows"]), auc_trees)
    model_text = booster.model_to_string()
    blocks = reference.sample_blocks(spec, run.seed, int(mix["score_sample_blocks"]))
    scores = {b: tl._score_rows(booster, *spec.block_range(b)) for b in blocks}
    record = booster.train_record.snapshot()["trees"]
    per_tree = [r["hist_passes"] for r in record]
    sampled_rows = [r.get("sampled_rows", 0) for r in record]
    del booster, train_set
    gc.collect()
    require_sampled(sampled_rows[warm:], spec.rows, ref_params.top_rate + ref_params.other_rate)

    # ---- correct -------------------------------------------------------
    t = time.perf_counter()
    numbers, trees, *_ = reference_goss.compare_run(
        spec, run.seed, ref_params, model_text, classes, unsampled, scores, xh, prob, auc_trees)
    if len(trees) != warm + win["trees"]:
        raise RuntimeError(f"model has {len(trees)} trees; {warm} + {win['trees']} were grown")
    correct, checks = reference.judge(numbers, cfg["limits"])
    reference_s = time.perf_counter() - t
    log(f"reference: {reference_s:.1f}s")

    counters = {
        "generate_s": generate_s, "binning_s": binning_s, "warmup_s": warmup_s,
        "window_trees": win["trees"], "window_s": win["seconds"],
        "update_returned_s": win["update_returned_s"], "hist_passes": per_tree[warm:],
        "sampled_rows": sampled_rows[warm:],
        "traced_trees": int(mix["trace_trees"]) if run.trace else 0,
        "memory_peak_bytes": memory_peak,
        "compiles_in_window": compiles_in_window, "reference_s": reference_s,
    }
    trace = None
    if run.trace:
        trace = trace_reduce.Reduced(*trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    return {
        "end_to_end": {"train_iters_per_s": win["trees"] / win["seconds"],
                       "heldout_auc_6": reference.auc(yh, prob), "setup_s": setup_s},
        "facts": Facts(cfg, run.device, peaks, counters, trace),
        "attempted": win["trees"], "failed": 0,
        "correct": correct, "checks": checks, "memory_peak_bytes": memory_peak,
        "notes": counters,
    }
