"""Serving latency benchmark: dense-compiler vs sequential-walk matrix.

Measures the steady-state request path (pad -> jitted bucket program ->
host copy) on warm CompiledPredictors for BOTH serving programs — the
inference compiler's fused dense program (``tpu_predict_compiler=dense``)
and the sequential per-tree walk (``walk``) — per shape bucket, per
model shape (num_trees x num_leaves), with and without categorical
splits.  Every dense row carries ``speedup_vs_walk`` against the
matching walk row; one bench-matrix-v1 JSON record for the CI artifact
(next to hist_kernel.py / many_models.py).

    python benchmarks/serve_latency.py                 # print rows
    python benchmarks/serve_latency.py --json out.json # + artifact

Env knobs: LAT_SHAPES ("50x63,200x7" = trees x leaves ladder),
LAT_BUCKETS ("64,512,4096"), LAT_REQUESTS (50 timed requests/rung),
LAT_FEATURES (28), LAT_ROWS (4000 training rows), LAT_CAT ("1" = also
run the categorical variants).

On non-TPU backends the dense rows measure the same program the MXU
runs but without the hardware the formulation targets (PERF.md round 4
measured the dense/walk ratio at ~70x per tree on TPU; round 13 records
the CPU-rung inversion) — rows carry the backend so regression diffs
compare like with like.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _git_sha():
    # same shape as the sibling benchmarks' helper (full sha, None on
    # failure) so artifact records join by git_sha across benches
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or None
    except Exception:
        return None


def _train(trees, leaves, feats, rows, cat):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(rows, feats).astype(np.float32)
    w = rng.randn(feats) / np.sqrt(feats)
    logit = X @ w
    cat_cols = []
    if cat:
        X[:, 3] = rng.randint(0, 48, rows)   # multi-word bitset (48 cats)
        logit = logit + (X[:, 3] % 3 == 0) * 1.2
        cat_cols = [3]
    y = ((logit + 0.5 * rng.randn(rows)) > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": leaves,
         "learning_rate": 0.1, "verbosity": -1}
    ds = lgb.Dataset(X, y, categorical_feature=cat_cols or "auto", params=p)
    return lgb.train(p, ds, trees)


def _measure(pred, Xq, reqs):
    """Timed requests only — callers warm the bucket first."""
    from lightgbm_tpu.telemetry.metrics import percentile as _pct
    lat = []
    for _ in range(reqs):
        t0 = time.perf_counter()
        pred.predict(Xq)
        lat.append((time.perf_counter() - t0) * 1e3)
    lat.sort()
    return _pct(lat, 50.0), _pct(lat, 99.0)


def _measure_split(pred, Xq, reqs, bucket):
    """Queue-wait vs device-compute split through the real micro-batcher
    (the per-request tracing path the serving tier runs): p50 of each
    component from the (model, bucket)-labeled timing histograms.  A
    small fixed sample suffices for a p50 split — the un-batched p50/p99
    measurement above already paid the full request count, so this must
    not double the ladder's wall time."""
    from lightgbm_tpu.serve.batcher import MicroBatcher
    from lightgbm_tpu.telemetry.metrics import percentile as _pct
    mb = MicroBatcher(pred.predict, stats=pred.stats, buckets=pred.buckets)
    try:
        for _ in range(min(int(reqs), 12)):
            mb.predict(Xq)
    finally:
        mb.close()
    t = pred.stats.bucket_timing(bucket)
    return {
        "request_p50_ms": round(_pct(t["request_latency_ms"], 50.0), 4),
        "queue_wait_p50_ms": round(_pct(t["queue_wait_ms"], 50.0), 4),
        "device_p50_ms": round(_pct(t["device_ms"], 50.0), 4),
    }


def main(argv) -> None:
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()
    json_path = ""
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]

    shapes = [tuple(int(v) for v in s.split("x"))
              for s in os.environ.get("LAT_SHAPES", "50x63,200x7").split(",")]
    buckets = [int(b) for b in
               os.environ.get("LAT_BUCKETS", "64,512,4096").split(",")]
    reqs = int(os.environ.get("LAT_REQUESTS", 50))
    feats = int(os.environ.get("LAT_FEATURES", 28))
    rows = int(os.environ.get("LAT_ROWS", 4000))
    with_cat = os.environ.get("LAT_CAT", "1") not in ("0", "false")

    from lightgbm_tpu.utils.backend import default_backend
    from lightgbm_tpu.utils.log import set_verbosity
    backend = default_backend()
    set_verbosity(-1)
    rng = np.random.RandomState(1)

    rows_out = []
    walk_p50 = {}
    for trees, leaves in shapes:
        for cat in ([False, True] if with_cat else [False]):
            bst = _train(trees, leaves, feats, rows, cat)
            preds = {}
            for path in ("walk", "dense"):
                try:
                    preds[path] = bst.to_predictor(warmup=False,
                                                   compiler=path)
                except Exception as e:  # noqa: BLE001 — record, keep going
                    rows_out.append({
                        "name": f"serve_{path}_{'cat' if cat else 'num'}"
                                f"_t{trees}x{leaves}",
                        "error": f"{type(e).__name__}: {e}"[:200]})
                    continue
            for bucket in buckets:
                Xq = rng.randn(bucket, feats).astype(np.float32)
                if cat:
                    Xq[:, 3] = rng.randint(0, 52, bucket)
                for path, pred in preds.items():
                    pred.predict(Xq)  # warm this bucket (unmeasured)
                    r0 = pred.stats.snapshot()["recompiles"]
                    p50, p99 = _measure(pred, Xq, reqs)
                    split = _measure_split(pred, Xq, reqs, bucket)
                    key = (trees, leaves, cat, bucket)
                    if path == "walk":
                        walk_p50[key] = p50
                    row = {
                        "name": f"serve_{path}_{'cat' if cat else 'num'}"
                                f"_t{trees}x{leaves}_b{bucket}",
                        "config": {"path": path, "cat": cat,
                                   "trees": trees, "leaves": leaves,
                                   "bucket": bucket, "features": feats,
                                   "backend": backend},
                        "p50_ms": round(p50, 4),
                        "p99_ms": round(p99, 4),
                        "rows_per_sec": round(bucket / (p50 / 1e3), 1),
                        # the per-request tracing split through the real
                        # micro-batcher path (queue wait vs device call)
                        **split,
                        "recompiles_after_warm": pred.stats.snapshot()[
                            "recompiles"] - r0,
                        "interpreted": False,
                    }
                    if path == "dense" and key in walk_p50:
                        row["speedup_vs_walk"] = round(
                            walk_p50[key] / p50, 3)
                    rows_out.append(row)
                    print(json.dumps(row), flush=True)

    if json_path:
        record = {
            "schema": "bench-matrix-v1",
            "bench": "serve_latency",
            "git_sha": _git_sha(),
            "backend": backend,
            "rows": rows_out,
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"written": json_path, "rungs": len(rows_out)}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
