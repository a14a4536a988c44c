"""Driver ``train_loop_sparse``: ``train_loop``'s steady-state boosting on
sparse one-hot rows with Exclusive Feature Bundling on.

The rows come from ``chipbench/datagen_sparse.py`` (the upstream's Allstate
benchmark in its one-hot coding: numeric columns and exactly exclusive
indicator columns) as 262,144-row CSR blocks, are stacked into ONE
``scipy.sparse.csr_matrix`` (float32 values, int32 indices), go to
``lgb.Dataset(csr, label)`` and are freed after ``construct()``.  The window
and the answers read back are ``train_loop``'s, and one more: the data set's
own list of the entries a bundle's conflict overwrote
(``Dataset.efb_conflicts()``).  ``chipbench/reference_sparse.py``, which has
no notion of a bundle, walks the raw columns and searches the indicator
columns again.

Before any data is made, a 64-row sparse booster with exclusive indicator
columns is built and one tree grown: a program whose ``TrainRecord`` states
no ``efb`` with fewer bundles than features ends there, in seconds, with exit
code 1.  After the window the run ends where the data set was not bundled to
under a tenth of its columns, where the grower did not route rows by its
kernel, or where the window's trees hold no split on a bundled column: the
cell's metrics would describe another program than the one that ran.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np
import scipy.sparse as sp

from chipbench import datagen_sparse, reference, reference_sparse, roofline, trace_reduce
from chipbench.drivers import train_loop as tl
from chipbench.drivers.train_loop import CompileCounter, predict_chunks, window_loop
from chipbench.facts import Facts


def probe_rows() -> tuple:
    """64 sparse rows: two numeric columns and two coded columns of 4 and 5
    exactly exclusive indicator columns, and a label that follows one level."""
    rng = np.random.default_rng(0)
    a, b = rng.integers(0, 4, 64), rng.integers(0, 5, 64)
    x = np.zeros((64, 11), np.float32)
    x[:, :2] = rng.standard_normal((64, 2))
    x[np.arange(64), 2 + a] = 1
    x[np.arange(64), 6 + b] = 1
    return sp.csr_matrix(x), (a >= 2).astype(np.float32)


def require_efb_record(lgb, params: dict) -> None:
    """A 64-row sparse booster with exclusive indicator columns, one tree,
    routed as the configuration's ``params`` route (their ``tpu_*`` and
    ``tree_grow_mode`` keys, if any); SystemExit where the program's record
    does not state ``efb`` with fewer bundles than features."""
    x, y = probe_rows()
    # the histogram implementation and its pipeline are named, not left to the
    # program's timing of its variants on 64 rows (learner/autotune.py): a
    # winner other than the Pallas kernel takes another grower
    probe = dict(objective="binary", num_leaves=4, min_data_in_bin=1, min_data_in_leaf=1,
                 min_sum_hessian_in_leaf=0.0, enable_bundle=True, verbosity=-1,
                 tpu_histogram_impl="pallas", tpu_pallas_pipeline="dma")
    probe.update({k: v for k, v in params.items()
                  if k.startswith("tpu_") or k == "tree_grow_mode"})
    try:
        booster = lgb.Booster(params=probe, train_set=lgb.Dataset(x, y, params=probe))
        snap = booster.train_record.snapshot()
        if snap.get("efb"):
            booster.update()
            snap = booster.train_record.snapshot()
    except Exception as exc:
        raise SystemExit(f"this program trains no booster on sparse exclusive columns "
                         f"({type(exc).__name__}: {exc}). Not running.")
    efb = snap.get("efb") or {}
    if not efb or not efb.get("bundles", 0) < efb.get("features", 0):
        raise SystemExit("this program's TrainRecord states no 'efb' with fewer bundles than "
                         "features: it cannot say whether it bundled the exclusive columns, "
                         "how many rows a conflict overwrote, or how its grower read the "
                         "bundles. Not running.")


def require_paths(record: dict, indicator_splits: list) -> None:
    """The record's own statement of the bundling and of the grower's static
    paths, and the window's splits, against what the cell describes."""
    efb, grower = record.get("efb") or {}, record.get("grower") or {}
    if not efb or not efb["bundles"] * 10 < efb["features"]:
        raise SystemExit(f"the data set was not bundled to under a tenth of its columns "
                         f"({efb}). Not this cell.")
    if not grower.get("efb"):
        raise SystemExit("the grower states no efb. Not this cell.")
    if grower.get("row_update") != "kernel":
        raise SystemExit(f"the grower routed rows by {grower.get('row_update')!r}, not by its "
                         f"kernel. Not this cell.")
    if not any(indicator_splits):
        raise SystemExit("no tree of the window splits on a bundled column. Not this cell.")


def run(run) -> dict:
    cfg, mix, log = run.config, run.mix, run.log
    spec = datagen_sparse.SparseSpec(cfg["data"])
    params = dict(cfg["params"], verbosity=-1)
    ref_params = reference_sparse.Params(cfg["params"])
    peaks = roofline.load_peaks(run.device["kind"])

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {configure_compile_cache()}; host cores {os.cpu_count()}, "
        f"free memory {tl._free_host_gb():.1f} GB")
    require_efb_record(lgb, cfg["params"])

    # ---- set-up --------------------------------------------------------
    compiles = CompileCounter()
    t = time.perf_counter()
    tables = datagen_sparse.Tables(spec)
    x, y = datagen_sparse.training_matrix(spec, run.seed, tables)
    xh, yh = datagen_sparse.holdout(spec, run.seed, tables)
    generate_s = time.perf_counter() - t
    log(f"data: {spec.rows}+{spec.holdout_rows} x {spec.features} made in {generate_s:.1f}s as "
        f"one csr matrix, {x.nnz / spec.rows:.2f} stored values a row ({x.data.dtype}, "
        f"{x.indices.dtype}); free memory {tl._free_host_gb():.1f} GB")
    t = time.perf_counter()
    train_set = lgb.Dataset(x, y, params=params)
    train_set.construct()
    binning_s = time.perf_counter() - t
    del x
    gc.collect()
    conflicts = reference_sparse.Conflicts(*train_set.efb_conflicts(),
                                           train_set.efb.record()["conflict_rows"])
    log(f"binning: {binning_s:.1f}s; {train_set.efb.record()}; "
        f"free memory {tl._free_host_gb():.1f} GB")
    t = time.perf_counter()
    booster = lgb.Booster(params=params, train_set=train_set)
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
    memory_peak = tl._memory_peak()
    compiles_in_window = compiles.count - compiles_before
    log(f"window: {win['trees']} trees in {win['seconds']:.2f}s; "
        f"compiles in window {compiles_in_window}; peak {memory_peak / 1e9:.2f} GB")

    # ---- the program's answers ----------------------------------------
    warm = int(mix["warmup_trees"])
    auc_trees = int(mix["auc_trees"])
    t = time.perf_counter()
    with span(trace_reduce.SPAN_PREFIX + "predict"):
        prob = predict_chunks(booster, datagen_sparse.Rows(xh),
                              int(mix["predict_chunk_rows"]), auc_trees)
    predict_s = time.perf_counter() - t
    model_text = booster.model_to_string()
    sampled = reference.sample_blocks(spec, run.seed, int(mix["score_sample_blocks"]))
    scores = {b: tl._score_rows(booster, *spec.block_range(b)) for b in sampled}
    record = booster.train_record.snapshot()
    per_tree = [r["hist_passes"] for r in record["trees"]]
    del booster, train_set
    gc.collect()
    log(f"answers read back; {spec.holdout_rows} held-out rows predicted in {predict_s:.1f}s")

    # ---- correct -------------------------------------------------------
    t = time.perf_counter()
    numbers, trees, _ = reference_sparse.compare_run(
        spec, run.seed, ref_params, model_text, scores, xh, prob, auc_trees, conflicts)
    if len(trees) != warm + win["trees"]:
        raise RuntimeError(f"model has {len(trees)} trees; {warm} + {win['trees']} were grown")
    indicator_splits = reference_sparse.indicator_split_counts(spec, trees)
    require_paths(record, indicator_splits[warm:])
    correct, checks = reference.judge(numbers, cfg["limits"])
    reference_s = time.perf_counter() - t
    log(f"reference: {reference_s:.1f}s")

    counters = {
        "generate_s": generate_s, "binning_s": binning_s, "warmup_s": warmup_s,
        "window_trees": win["trees"], "window_s": win["seconds"],
        "update_returned_s": win["update_returned_s"], "hist_passes": per_tree[warm:],
        "indicator_splits": indicator_splits[warm:],
        "internal_nodes": [t.num_leaves - 1 for t in trees[warm:]],
        "traced_trees": int(mix["trace_trees"]) if run.trace else 0,
        "memory_peak_bytes": memory_peak, "predict_s": predict_s,
        "compiles_in_window": compiles_in_window, "reference_s": reference_s,
        # the program's own statement of its bundling and of its grower's static paths
        "efb": record.get("efb", {}), "grower": record.get("grower", {}),
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
