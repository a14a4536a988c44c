"""Driver ``train_loop``: steady-state boosting through the public training
path, ``lgb.Dataset`` -> ``lgb.Booster`` -> ``Booster.update()``.

Set-up: data from the seed, host binning, upload, compile or cache load, the
mix's warm-up trees.  Window: ``update()`` after ``update()`` until the host
clock has passed ``--seconds`` (and the mix's least number of trees is done),
no further tree started, one forcing scalar copy, clock stopped.  The rate is
whole trees over the whole of that time.  Then the program's answers are read
back (model text, held-out predictions, a sample of the training scores), its
state is freed, and the plain reference decides ``correct``.
"""

from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time

import numpy as np

from chipbench import datagen, reference, roofline, trace_reduce
from chipbench.facts import Facts


def _force(booster) -> float:
    """One scalar host copy that waits for every queued device computation
    (``bench.py`` ``sync()``)."""
    import jax.numpy as jnp
    return float(jnp.sum(booster._gbdt.score))


def _score_rows(booster, lo: int, hi: int) -> np.ndarray:
    return np.asarray(booster._gbdt.score[lo:hi])


def _memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks))


def predict_chunks(booster, x: np.ndarray, chunk: int, num_iteration: int) -> np.ndarray:
    """The dense predictor compiles one program per row bucket, and its
    compile time grows with the bucket (PERF.md): many small calls."""
    return np.concatenate([booster.predict(x[lo:lo + chunk], num_iteration=num_iteration)
                           for lo in range(0, len(x), chunk)])


def window_loop(update, force, seconds: float, min_trees: int,
                clock=time.perf_counter, first_trees=None) -> dict:
    """The measured window.  ``first_trees(update)``, if given, grows the first
    few trees itself (the traced ones) and returns how many.  Returns whole
    trees and the whole time, the final force included, and when each later
    ``update()`` returned (it returns once the tree before it is done on the
    device: a run that reads far off shows there where it lost its time)."""
    t0 = clock()
    trees = first_trees(update) if first_trees is not None else 0
    returned = []
    while trees < min_trees or clock() - t0 < seconds:
        update()
        trees += 1
        returned.append(clock() - t0)
    force()
    t1 = clock()
    return {"trees": trees, "seconds": t1 - t0, "update_returned_s": returned}


def run(run) -> dict:
    cfg, mix, log = run.config, run.mix, run.log
    spec = datagen.TabularSpec(cfg["data"])
    params = dict(cfg["params"], verbosity=-1)
    peaks = roofline.load_peaks(run.device["kind"])

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    # every program goes to the cache, also those that compile in under a second:
    # the second run of a cell in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {configure_compile_cache()}; host cores {os.cpu_count()}, "
        f"free memory {_free_host_gb():.1f} GB")

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
    for _ in range(int(mix["warmup_trees"])):
        booster.update()
    _force(booster)
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
            _force(booster)

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
    memory_peak = _memory_peak()
    compiles_in_window = compiles.count - compiles_before
    log(f"window: {win['trees']} trees in {win['seconds']:.2f}s; "
        f"compiles in window {compiles_in_window}; peak {memory_peak / 1e9:.2f} GB")

    # ---- the program's answers ----------------------------------------
    warm = int(mix["warmup_trees"])
    auc_trees = int(mix["auc_trees"])
    with span(trace_reduce.SPAN_PREFIX + "predict"):
        prob = predict_chunks(booster, xh, int(mix["predict_chunk_rows"]), auc_trees)
    model_text = booster.model_to_string()
    blocks = reference.sample_blocks(spec, run.seed, int(mix["score_sample_blocks"]))
    scores = {b: _score_rows(booster, *spec.block_range(b)) for b in blocks}
    per_tree = [r["hist_passes"] for r in booster.train_record.snapshot()["trees"]]
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

    window_passes = per_tree[warm:]
    counters = {
        "generate_s": generate_s, "binning_s": binning_s, "warmup_s": warmup_s,
        "window_trees": win["trees"], "window_s": win["seconds"],
        "update_returned_s": win["update_returned_s"], "hist_passes": window_passes,
        "traced_trees": int(mix["trace_trees"]) if run.trace else 0,
        "memory_peak_bytes": memory_peak,
        "compiles_in_window": compiles_in_window, "reference_s": reference_s,
    }
    trace = None
    if run.trace:
        # the trace stays on disk until the next traced run of this cell replaces it
        trace = trace_reduce.Reduced(*trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    return {
        "end_to_end": {"train_iters_per_s": win["trees"] / win["seconds"],
                       "heldout_auc_6": reference.auc(yh, prob), "setup_s": setup_s},
        "facts": Facts(cfg, run.device, peaks, counters, trace),
        "attempted": win["trees"], "failed": 0,
        "correct": correct, "checks": checks, "memory_peak_bytes": memory_peak,
        "notes": counters,
    }


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads as JAX's own
    monitoring reports them: none may fall inside the window."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if "backend_compile" in event or "cache_retrieval" in event:
            self.count += 1


def _free_host_gb() -> float:
    with contextlib.suppress(OSError, ValueError, IndexError):
        with open("/proc/meminfo") as fh:
            for line in fh:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) / 1e6
    return float("nan")
