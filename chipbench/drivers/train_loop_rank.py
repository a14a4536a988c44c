"""Driver ``train_loop_rank``: ``train_loop``'s steady-state boosting of a
LambdaMART ranker, ``objective=lambdarank``.

The rows come from ``chipbench/datagen_rank.py`` as query-aligned float32
blocks and go to ``lgb.Dataset(blocks, label, group=sizes)``; they are freed
after ``construct()``.  The window is ``train_loop``'s.  The answers read
back are the model text, the raw scores of the held-out queries with the
first ``auc_trees`` trees, and, on ``sampled_queries`` whole training
queries drawn from the seed (the longest, the shortest, one at the
truncation level, one under it, one over 1,000 documents among them), the
final training scores and the lambdas and hessians the program's objective
gives for them, read once after the window.  ``chipbench/reference_rank.py``
decides ``correct``.  ``heldout_auc_6`` is the within-query pairwise AUC of
the held-out queries; NDCG@10 goes into the notes.

Before any data is made a lambdarank booster on three small queries is
built: a program whose ``TrainRecord`` states no query layout (the
objective's ``pair_slots`` and ``pairs_counted``) ends there, in seconds,
with exit code 1: its lambdas could not be told apart from a pair tensor
padded to the longest query, which at this cell's size costs minutes a
tree.  After the window the run ends where the layout the record states
is not this cell's queries.
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from chipbench import datagen_rank, reference_rank, roofline, trace_reduce
from chipbench.drivers import train_loop as tl
from chipbench.drivers.train_loop import CompileCounter, predict_chunks, window_loop
from chipbench.facts import Facts

LAYOUT_KEYS = ("queries", "buckets", "row_slots", "pair_slots", "pairs_counted")


def require_layout_record(lgb, params: dict) -> None:
    """Three queries (2, 5 and 40 documents) through ``lgb.Dataset(...,
    group=)`` and a lambdarank booster at the configuration's truncation;
    SystemExit where the program's record states no query layout."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((47, 3)).astype(np.float32)
    y = rng.integers(0, 3, 47).astype(np.float32)
    probe = dict(objective="lambdarank", num_leaves=4, min_data_in_bin=1, min_data_in_leaf=1,
                 min_sum_hessian_in_leaf=0.0, verbosity=-1,
                 lambdarank_truncation_level=params.get("lambdarank_truncation_level", 30))
    try:
        booster = lgb.Booster(params=probe, train_set=lgb.Dataset(
            [x[:20], x[20:]], y, group=[2, 5, 40], params=probe))
        snap = booster.train_record.snapshot()
    except Exception as exc:
        raise SystemExit(f"this program trains no lambdarank booster on row blocks with "
                         f"groups ({type(exc).__name__}: {exc}). Not running.")
    layout = snap.get("objective") or {}
    if any(k not in layout for k in LAYOUT_KEYS):
        raise SystemExit("this program's TrainRecord states no query layout of its "
                         "lambdarank objective (pair_slots, pairs_counted): it cannot say "
                         "how many pairs its lambdas evaluate, and a pair tensor padded to "
                         "the longest query costs minutes a tree at this cell's size. "
                         "Not running.")


def require_layout(layout: dict, spec, truncation: int) -> None:
    """The record's statement of the layout against the cell's queries."""
    want = {"queries": spec.queries,
            "pairs_counted": reference_rank.pairs_visited(spec.lengths, truncation)}
    if (layout.get("queries") != want["queries"] or layout.get("row_slots", 0) < spec.rows or
            layout.get("pairs_counted") != want["pairs_counted"]):
        raise SystemExit(f"the objective laid out {layout}; the cell's queries are {want} "
                         f"with {spec.rows} rows. Not this cell.")


def read_sample(booster, spec, seed: int, k: int, truncation: int):
    """What the program gives on ``k`` whole training queries drawn from the
    seed: its final training scores, and the lambdas and hessians its
    objective computes from them (one more gradient step, outside the window)."""
    queries = reference_rank.sample_queries(spec, seed, k, truncation)
    rows = reference_rank.query_rows(spec, queries)
    gbdt = booster._gbdt
    lam, hess = gbdt.objective.get_gradients(gbdt.score)
    return reference_rank.Sample(queries, np.asarray(gbdt.score)[rows],
                                 np.asarray(lam)[rows], np.asarray(hess)[rows])


def run(run) -> dict:
    cfg, mix, log = run.config, run.mix, run.log
    spec = datagen_rank.RankSpec(cfg["data"])
    params = dict(cfg["params"], verbosity=-1)
    ref_params = reference_rank.Params(cfg["params"])
    peaks = roofline.load_peaks(run.device["kind"])

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.cache import configure_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"compile cache: {configure_compile_cache()}; host cores {os.cpu_count()}, "
        f"free memory {tl._free_host_gb():.1f} GB")
    require_layout_record(lgb, cfg["params"])

    # ---- set-up --------------------------------------------------------
    compiles = CompileCounter()
    t = time.perf_counter()
    blocks, y = datagen_rank.training_blocks(spec, run.seed)
    xh, yh, lengths_h = datagen_rank.holdout(spec, run.seed)
    generate_s = time.perf_counter() - t
    log(f"data: {spec.rows} rows in {spec.queries} queries (longest {spec.lengths.max()}) + "
        f"{spec.holdout_rows} held-out rows in {spec.holdout_queries} queries x {spec.features} "
        f"made in {generate_s:.1f}s as {len(blocks)} query-aligned {blocks[0].dtype} blocks; "
        f"free memory {tl._free_host_gb():.1f} GB")
    t = time.perf_counter()
    train_set = lgb.Dataset(blocks, y, group=spec.lengths, params=params)
    train_set.construct()
    binning_s = time.perf_counter() - t
    del blocks
    gc.collect()
    log(f"binning: {binning_s:.1f}s; free memory {tl._free_host_gb():.1f} GB")
    t = time.perf_counter()
    booster = lgb.Booster(params=params, train_set=train_set)
    layout = booster.train_record.snapshot().get("objective") or {}
    require_layout(layout, spec, ref_params.truncation)
    for _ in range(int(mix["warmup_trees"])):
        booster.update()
    tl._force(booster)
    warmup_s = time.perf_counter() - t
    log(f"upload, compile or cache load, {mix['warmup_trees']} warm-up trees: {warmup_s:.1f}s; "
        f"layout {layout}")

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
        pred = predict_chunks(booster, xh, int(mix["predict_chunk_rows"]), auc_trees)
    predict_s = time.perf_counter() - t
    model_text = booster.model_to_string()
    sample = read_sample(booster, spec, run.seed, int(mix["sampled_queries"]),
                         ref_params.truncation)
    record = booster.train_record.snapshot()
    per_tree = [r["hist_passes"] for r in record["trees"]]
    del booster, train_set
    gc.collect()
    log(f"answers read back; {spec.holdout_rows} held-out rows predicted in {predict_s:.1f}s; "
        f"{len(sample.queries)} sampled queries, {len(sample.scores)} rows")

    # ---- correct -------------------------------------------------------
    t = time.perf_counter()
    numbers, trees = reference_rank.compare_run(spec, run.seed, ref_params, model_text,
                                                sample, xh, pred, auc_trees)
    if len(trees) != warm + win["trees"]:
        raise RuntimeError(f"model has {len(trees)} trees; {warm} + {win['trees']} were grown")
    correct, checks = reference_rank.judge(numbers, cfg["limits"])
    reference_s = time.perf_counter() - t
    log(f"reference: {reference_s:.1f}s")

    counters = {
        "generate_s": generate_s, "binning_s": binning_s, "warmup_s": warmup_s,
        "window_trees": win["trees"], "window_s": win["seconds"],
        "update_returned_s": win["update_returned_s"], "hist_passes": per_tree[warm:],
        "internal_nodes": [t.num_leaves - 1 for t in trees[warm:]],
        "traced_trees": int(mix["trace_trees"]) if run.trace else 0,
        "memory_peak_bytes": memory_peak, "predict_s": predict_s,
        "compiles_in_window": compiles_in_window, "reference_s": reference_s,
        "ndcg_10": reference_rank.ndcg_at(yh, pred, lengths_h, 10),
        "sampled_query_lengths": [int(n) for n in spec.lengths[sample.queries]],
        # the program's own statement of its query layout and its grower's paths
        "rank_layout": layout, "grower": record.get("grower", {}),
    }
    trace = None
    if run.trace:
        trace = trace_reduce.Reduced(*trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    return {
        "end_to_end": {"train_iters_per_s": win["trees"] / win["seconds"],
                       "heldout_auc_6": reference_rank.query_auc(yh, pred, lengths_h),
                       "setup_s": setup_s},
        "facts": Facts(cfg, run.device, peaks, counters, trace),
        "attempted": win["trees"], "failed": 0,
        "correct": correct, "checks": checks, "memory_peak_bytes": memory_peak,
        "notes": counters,
    }
