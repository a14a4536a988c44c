"""Driver ``train_loop_cat``: ``train_loop_blocks``' steady-state boosting on
integer + categorical columns with missing values.

The rows come from ``chipbench/datagen_ctr.py`` (the raw Criteo schema: count
columns and label-encoded categorical columns, NaN where missing) as 262,144-row
float32 blocks, go to ``lgb.Dataset(data=[block, ...], categorical_feature=...)``
as they are made and are freed after ``construct()``.  The window and the
answers read back are ``train_loop``'s; ``chipbench/reference_cat.py`` walks
bitsets and NaN directions on raw values and redoes the categorical search.

Before any data is made, a 64-row booster with one categorical column is built
and one tree grown: a program whose ``TrainRecord`` states no ``grower`` paths
or no ``cat_splits`` ends there, in seconds, with exit code 1 (it would route
every row of every wave through an XLA gather).  After the window the run ends
where the grower did not route rows by its kernel, where it bundled columns
(EFB), or where the window's trees hold no categorical split: the cell's
metrics would describe another program than the one that ran.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from chipbench import datagen_ctr, reference, reference_cat, roofline, trace_reduce
from chipbench.drivers import train_loop as tl
from chipbench.drivers.train_loop import CompileCounter, predict_chunks, window_loop
from chipbench.facts import Facts


def require_cat_record(lgb, params: dict) -> None:
    """A 64-row booster with one integer and one categorical column, one tree,
    routed as the configuration's ``params`` route (their ``tpu_*`` and
    ``tree_grow_mode`` keys, if any); SystemExit where the program's record
    says neither which static paths its grower was built with nor how many of
    a tree's splits are categorical."""
    rng = np.random.default_rng(0)
    cat = rng.integers(0, 4, 64)
    x = np.stack([rng.integers(0, 9, 64), cat], axis=1).astype(np.float32)
    y = (cat >= 2).astype(np.float32)
    # 64 rows are few enough for the program to time its histogram variants on
    # them and keep the winner (learner/autotune.py; the cell's rows are far
    # past that size): at this size the timing is noise, and a winner other
    # than the Pallas kernel takes another grower, which states no paths.  So
    # the probe names what "auto" is at the cell's size on a TPU.
    probe = dict(objective="binary", num_leaves=4, min_data_in_bin=1, min_data_in_leaf=1,
                 min_data_per_group=1, cat_smooth=0.0, categorical_feature=[1], verbosity=-1,
                 tpu_histogram_impl="pallas")
    probe.update({k: v for k, v in params.items()
                  if k.startswith("tpu_") or k == "tree_grow_mode"})
    try:
        booster = lgb.Booster(params=probe, train_set=lgb.Dataset(
            x, y, params=probe, categorical_feature=[1]))
        snap = booster.train_record.snapshot()
        if snap.get("grower"):
            booster.update()
            snap = booster.train_record.snapshot()
    except Exception as exc:
        raise SystemExit(f"this program trains no booster with a categorical column "
                         f"({type(exc).__name__}: {exc}). Not running.")
    if not snap.get("grower"):
        raise SystemExit("this program's TrainRecord has no 'grower': it cannot say whether "
                         "its row update ran as the kernel, and a data set with categorical "
                         "columns may take an XLA gather over every row. Not running.")
    if not snap.get("trees") or "cat_splits" not in snap["trees"][0]:
        raise SystemExit("this program's TrainRecord counts no cat_splits a tree. Not running.")


def require_paths(grower: dict, cat_splits: list) -> None:
    """The grower's own statement of its static paths, and the window's
    categorical splits, against what the cell describes."""
    if grower.get("row_update") != "kernel":
        raise SystemExit(f"the grower routed rows by {grower.get('row_update')!r}, not by its "
                         f"kernel. Not this cell.")
    if grower.get("efb"):
        raise SystemExit("the data set was bundled (EFB): the cell states raw columns.")
    if not any(cat_splits):
        raise SystemExit("no tree of the window holds a categorical split. Not this cell.")


def run(run) -> dict:
    cfg, mix, log = run.config, run.mix, run.log
    spec = datagen_ctr.CtrSpec(cfg["data"])
    params = dict(cfg["params"], verbosity=-1)
    ref_params = reference_cat.Params(cfg["params"])
    peaks = roofline.load_peaks(run.device["kind"])
    if list(cfg["params"]["categorical_feature"]) != spec.categorical_feature:
        raise SystemExit("configuration: params.categorical_feature is not the data group's "
                         "categorical columns")

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {configure_compile_cache()}; host cores {os.cpu_count()}, "
        f"free memory {tl._free_host_gb():.1f} GB")
    require_cat_record(lgb, cfg["params"])

    # ---- set-up --------------------------------------------------------
    compiles = CompileCounter()
    t = time.perf_counter()
    tables = datagen_ctr.Tables(spec)
    blocks, y = datagen_ctr.training_blocks(spec, run.seed, tables)
    xh, yh = datagen_ctr.holdout(spec, run.seed, tables)
    generate_s = time.perf_counter() - t
    log(f"data: {spec.rows}+{spec.holdout_rows} x {spec.n_int}+{spec.n_cat} made in "
        f"{generate_s:.1f}s as {len(blocks)} {blocks[0].dtype} blocks; "
        f"free memory {tl._free_host_gb():.1f} GB")
    t = time.perf_counter()
    train_set = lgb.Dataset(blocks, y, params=params,
                            categorical_feature=spec.categorical_feature)
    train_set.construct()
    binning_s = time.perf_counter() - t
    del blocks
    gc.collect()
    log(f"binning: {binning_s:.1f}s; free memory {tl._free_host_gb():.1f} GB")
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
    with span(trace_reduce.SPAN_PREFIX + "predict"):
        prob = predict_chunks(booster, xh, int(mix["predict_chunk_rows"]), auc_trees)
    model_text = booster.model_to_string()
    sampled = reference.sample_blocks(spec, run.seed, int(mix["score_sample_blocks"]))
    scores = {b: tl._score_rows(booster, *spec.block_range(b)) for b in sampled}
    record = booster.train_record.snapshot()
    per_tree = [r["hist_passes"] for r in record["trees"]]
    cat_splits = [r["cat_splits"] for r in record["trees"]]
    del booster, train_set
    gc.collect()
    require_paths(record.get("grower") or {}, cat_splits[warm:])

    # ---- correct -------------------------------------------------------
    t = time.perf_counter()
    numbers, trees, *_ = reference_cat.compare_run(
        spec, run.seed, ref_params, model_text, scores, xh, prob, auc_trees)
    if len(trees) != warm + win["trees"]:
        raise RuntimeError(f"model has {len(trees)} trees; {warm} + {win['trees']} were grown")
    if [int(t.is_cat.sum()) for t in trees] != cat_splits:
        raise RuntimeError("the record's cat_splits are not the model text's categorical nodes")
    correct, checks = reference.judge(numbers, cfg["limits"])
    reference_s = time.perf_counter() - t
    log(f"reference: {reference_s:.1f}s")

    counters = {
        "generate_s": generate_s, "binning_s": binning_s, "warmup_s": warmup_s,
        "window_trees": win["trees"], "window_s": win["seconds"],
        "update_returned_s": win["update_returned_s"], "hist_passes": per_tree[warm:],
        "cat_splits": cat_splits[warm:],
        "internal_nodes": [t.num_leaves - 1 for t in trees[warm:]],
        "traced_trees": int(mix["trace_trees"]) if run.trace else 0,
        "memory_peak_bytes": memory_peak,
        "compiles_in_window": compiles_in_window, "reference_s": reference_s,
        # the program's own statement of the static paths its grower took
        "grower": record.get("grower", {}),
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
