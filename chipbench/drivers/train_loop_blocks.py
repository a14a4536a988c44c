"""Driver ``train_loop_blocks``: ``train_loop``'s steady-state boosting, with
the training rows handed to ``lgb.Dataset`` as a list of row blocks.

The rows of a four-chip cell do not fit the host as one float64 matrix, let
alone three; a host of a data-parallel job reads its partition in pieces
anyway.  So the generator's own 262,144-row float32 blocks go to
``lgb.Dataset(data=[block, ...])`` as they are made, the program bins them one
by one, and they are freed after ``construct()``.  The window, the answers
read back and the plain reference are ``train_loop``'s; the mesh is the
program's business (``tree_learner`` and the visible devices).  Beside
``train_loop``'s counters this one keeps the program's own record of its
collectives and its mesh, and every device's peak bytes.

Before any data is made, a Dataset is built from two 8-row blocks: a program
without block input ends there, in seconds, with an error.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from chipbench import datagen, reference, roofline, trace_reduce
from chipbench.drivers import train_loop as tl
from chipbench.drivers.train_loop import CompileCounter, predict_chunks, window_loop
from chipbench.facts import Facts


def require_block_input(lgb) -> None:
    """Two 8-row blocks through ``lgb.Dataset``; SystemExit where the program
    does not take them as the rows of one matrix."""
    blocks = [np.arange(lo, lo + 16, dtype=np.float32).reshape(8, 2) for lo in (0, 16)]
    try:
        probe = lgb.Dataset(blocks, np.zeros(16, np.float32),
                            params={"min_data_in_bin": 1, "min_data_in_leaf": 1, "verbosity": -1})
        probe.construct()
        shape = (probe.num_data(), probe.num_feature())
    except Exception as exc:
        raise SystemExit(f"this program's lgb.Dataset takes no list of row blocks "
                         f"({type(exc).__name__}: {exc}); the cell feeds it nothing else. "
                         f"Not running.")
    if shape != (16, 2):
        raise SystemExit(f"lgb.Dataset read two 8 x 2 row blocks as {shape}, not (16, 2). "
                         f"Not running.")


def training_blocks(spec: datagen.TabularSpec, seed: int):
    """([float32 (n_b, F) per generator block], float32 (rows,)), made on a
    few threads; no block is ever joined to another."""
    w = datagen.weights(spec)
    made = datagen.map_blocks(spec, lambda b: datagen.block(spec, seed, b, w))
    return [x for x, _ in made], np.concatenate([y for _, y in made])


def require_mesh(built: dict, stated: dict) -> None:
    """The program's own record of its mesh against the configuration's
    ``mesh`` group, whose rows a chip the mesh readers divide by: on more
    visible devices, or with ``tree_learner`` ignored, they would read a
    multiple of the truth with ``correct`` still true."""
    got = {k: built.get(k) for k in ("chips", "rows_per_chip")}
    want = {k: stated[k] for k in ("chips", "rows_per_chip")}
    if got != want:
        raise SystemExit(f"the program built the mesh {got}; the configuration states "
                         f"{want}. Not running.")


def device_peaks() -> list:
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.local_devices()]


def run(run) -> dict:
    cfg, mix, log = run.config, run.mix, run.log
    spec = datagen.TabularSpec(cfg["data"])
    params = dict(cfg["params"], verbosity=-1)
    peaks = roofline.load_peaks(run.device["kind"])

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {configure_compile_cache()}; host cores {os.cpu_count()}, "
        f"free memory {tl._free_host_gb():.1f} GB")
    require_block_input(lgb)

    # ---- set-up --------------------------------------------------------
    compiles = CompileCounter()
    t = time.perf_counter()
    blocks, y = training_blocks(spec, run.seed)
    xh, yh = datagen.holdout(spec, run.seed)
    generate_s = time.perf_counter() - t
    log(f"data: {spec.rows}+{spec.holdout_rows} x {spec.features} made in {generate_s:.1f}s "
        f"as {len(blocks)} {blocks[0].dtype} blocks; free memory {tl._free_host_gb():.1f} GB")
    t = time.perf_counter()
    train_set = lgb.Dataset(blocks, y, params=params)
    train_set.construct()
    binning_s = time.perf_counter() - t
    del blocks
    gc.collect()
    log(f"binning: {binning_s:.1f}s; free memory {tl._free_host_gb():.1f} GB")
    t = time.perf_counter()
    booster = lgb.Booster(params=params, train_set=train_set)
    require_mesh(booster.train_record.snapshot().get("mesh") or {}, cfg["mesh"])
    for _ in range(int(mix["warmup_trees"])):
        booster.update()
    tl._force(booster)
    warmup_s = time.perf_counter() - t
    log(f"upload, compile or cache load, {mix['warmup_trees']} warm-up trees: {warmup_s:.1f}s")

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
    device_peak_bytes = device_peaks()
    memory_peak = max(device_peak_bytes)
    compiles_in_window = compiles.count - compiles_before
    log(f"window: {win['trees']} trees in {win['seconds']:.2f}s; compiles in window "
        f"{compiles_in_window}; peak bytes in use by device {device_peak_bytes}")

    # ---- the program's answers ----------------------------------------
    warm = int(mix["warmup_trees"])
    auc_trees = int(mix["auc_trees"])
    with span(trace_reduce.SPAN_PREFIX + "predict"):
        prob = predict_chunks(booster, xh, int(mix["predict_chunk_rows"]), auc_trees)
    model_text = booster.model_to_string()
    sampled = reference.sample_blocks(spec, run.seed, int(mix["score_sample_blocks"]))
    scores = {b: tl._score_rows(booster, *spec.block_range(b)) for b in sampled}
    record = booster.train_record.snapshot()
    per_tree = [r["hist_passes"] for r in record["trees"]]
    del booster, train_set
    gc.collect()

    # ---- correct -------------------------------------------------------
    t = time.perf_counter()
    numbers, trees, *_ = reference.compare_run(
        spec, run.seed, reference.Params(cfg["params"]), model_text, scores, xh, prob, auc_trees)
    if len(trees) != warm + win["trees"]:
        raise RuntimeError(f"model has {len(trees)} trees; {warm} + {win['trees']} were grown")
    correct, checks = reference.judge(numbers, cfg["limits"])
    reference_s = time.perf_counter() - t
    log(f"reference: {reference_s:.1f}s")

    counters = {
        "generate_s": generate_s, "binning_s": binning_s, "warmup_s": warmup_s,
        "window_trees": win["trees"], "window_s": win["seconds"],
        "update_returned_s": win["update_returned_s"], "hist_passes": per_tree[warm:],
        "traced_trees": int(mix["trace_trees"]) if run.trace else 0,
        "memory_peak_bytes": memory_peak, "device_peak_bytes": device_peak_bytes,
        "compiles_in_window": compiles_in_window, "reference_s": reference_s,
        # the program's own record of what it traced and where it ran
        "collectives": record.get("collectives", {}), "mesh": record.get("mesh", {}),
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
