"""Benchmark harness over the BASELINE.json config matrix.

Reproduces the five reference benchmark shapes (docs/Experiments.rst +
BASELINE.json "configs") on synthetic data at a configurable scale, each
printing one JSON line in bench.py's schema.  The repo-root ``bench.py``
remains the driver-run headline (Higgs single-chip); this harness covers
the rest of the matrix:

    python benchmarks/run.py                 # all configs, SCALE=1
    python benchmarks/run.py higgs ranking   # subset
    SCALE=0.1 python benchmarks/run.py       # 10x smaller (CI/smoke)

Configs:
  higgs      10.5M x 28 dense binary, 255 leaves/bins (Experiments.rst:110)
  higgs_dp   same, tree_learner=data over all visible devices
  ranking    LambdaRank, MSLR-like query structure, feature-parallel
  multiclass Covertype-like 7-class + categoricals, GOSS
  sparse     Criteo-like wide one-hot sparse, EFB + voting-parallel

``--json out.json`` additionally writes one machine-trackable record for
the whole run (schema ``bench-matrix-v1``: git sha, backend, SCALE, and
the per-config name/config/iters_per_sec rows), so the perf trajectory
lands in BENCH_*.json-style artifacts instead of being hand-copied into
PERF.md.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCALE = float(os.environ.get("SCALE", 1.0))

# rows accumulated for the --json record (one per benched config)
_RECORDS = []


def _git_sha():
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or None
    except Exception:
        return None


def _emit(name, trees, dt, extra="", baseline=None, config=None):
    """One bench.py-schema JSON line.  ``baseline`` is the reference
    iters/s for THIS config when published (docs/Experiments.rst); the
    non-Higgs configs have no comparable published number and omit
    vs_baseline rather than ratio against a different workload."""
    ips = trees / dt
    rec = {
        "metric": f"boosting_iters_per_sec ({name}{extra})",
        "value": round(ips, 4),
        "unit": "iters/s",
    }
    if baseline:
        rec["vs_baseline"] = round(ips / baseline, 4)
    print(json.dumps(rec), flush=True)
    _RECORDS.append({
        "name": name,
        "iters_per_sec": round(ips, 4),
        "trees": trees,
        "seconds": round(dt, 3),
        **({"vs_baseline": round(ips / baseline, 4)} if baseline else {}),
        **({"config": config} if config else {}),
    })


HIGGS_CPU_BASELINE = 500.0 / 130.094   # == bench.py BASELINE_ITERS_PER_SEC


def _train(params, ds, trees, valid=None, warmup=1):
    import lightgbm_tpu as lgb
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(warmup):           # compile + first tree(s); GOSS
        bst.update()                  # configs warm past the 1/lr
    #                                   sampling boundary so its one-time
    #                                   recompile stays out of steady-state
    t0 = time.perf_counter()
    for _ in range(trees):
        bst.update()
    float(np.asarray(bst._gbdt.score).sum())
    return bst, time.perf_counter() - t0


def bench_higgs(tree_learner="serial"):
    import lightgbm_tpu as lgb
    n = int(10_500_000 * SCALE)
    rng = np.random.RandomState(0)
    X = rng.randn(n, 28).astype(np.float32)
    w = rng.randn(28) / np.sqrt(28)
    y = ((X @ w + 0.3 * np.sin(2 * X[:, 0]) * X[:, 1] +
          0.5 * rng.randn(n)) > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
         "learning_rate": 0.1, "verbosity": -1,
         "tree_learner": tree_learner}
    trees = int(os.environ.get("TREES", 25))
    _, dt = _train(p, lgb.Dataset(X, y, params=p), trees)
    _emit("higgs" if tree_learner == "serial" else "higgs_dp", trees, dt,
          f", {n}x28, tl={tree_learner}",
          # the published number is for the FULL 10.5M config only
          baseline=HIGGS_CPU_BASELINE if SCALE == 1.0 else None,
          config={**p, "rows": n, "features": 28})


def bench_ranking():
    import lightgbm_tpu as lgb
    nq = int(3000 * SCALE) or 10
    per_q = 120
    n = nq * per_q
    rng = np.random.RandomState(1)
    X = rng.randn(n, 64).astype(np.float32)
    w = rng.randn(64) / 8
    rel = X @ w + 0.7 * rng.randn(n)
    group = np.full(nq, per_q)
    y = np.zeros(n)
    for q in range(nq):  # per-query 5-level relevance
        s = rel[q * per_q:(q + 1) * per_q]
        y[q * per_q:(q + 1) * per_q] = np.digitize(
            s, np.quantile(s, [0.5, 0.75, 0.9, 0.97]))
    p = {"objective": "lambdarank", "num_leaves": 255, "max_bin": 255,
         "learning_rate": 0.1, "verbosity": -1,
         "tree_learner": "feature"}
    trees = int(os.environ.get("TREES", 25))
    ds = lgb.Dataset(X, y, group=group, params=p)
    _, dt = _train(p, ds, trees)
    _emit("ranking_lambdarank", trees, dt, f", {nq} queries, tl=feature",
          config={**p, "queries": nq, "rows": n, "features": 64})


def bench_multiclass():
    import lightgbm_tpu as lgb
    n = int(581_000 * SCALE) or 5000
    rng = np.random.RandomState(2)
    Xn = rng.randn(n, 10).astype(np.float32)
    cat = rng.randint(0, 40, (n, 2)).astype(np.float32)
    X = np.concatenate([Xn, cat], axis=1)
    logits = np.stack([Xn @ (rng.randn(10) / 3) +
                       (cat[:, 0] % 7 == c) * 1.5 for c in range(7)], 1)
    y = np.argmax(logits + 0.5 * rng.randn(n, 7), axis=1).astype(np.float64)
    p = {"objective": "multiclass", "num_class": 7, "num_leaves": 63,
         "max_bin": 255, "learning_rate": 0.1, "verbosity": -1,
         "boosting": "goss"}
    trees = int(os.environ.get("TREES", 10))
    ds = lgb.Dataset(X, y, categorical_feature=[10, 11], params=p)
    _, dt = _train(p, ds, trees, warmup=int(1.0 / p["learning_rate"]) + 2)
    _emit("multiclass_goss", trees, dt, f", {n}x12 7-class",
          config={**p, "rows": n, "features": 12})


def bench_sparse():
    import scipy.sparse as sp
    import lightgbm_tpu as lgb
    n = int(1_000_000 * SCALE) or 10_000
    f = 2000
    rng = np.random.RandomState(3)
    nnz_per_row = 25
    rows = np.repeat(np.arange(n), nnz_per_row)
    cols = rng.randint(0, f, n * nnz_per_row)
    vals = rng.rand(n * nnz_per_row).astype(np.float32) + 0.5
    X = sp.csr_matrix((vals, (rows, cols)), shape=(n, f))
    y = ((np.asarray(X[:, :50].sum(axis=1)).ravel() +
          0.5 * rng.randn(n)) > 12.5).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 127, "max_bin": 255,
         "learning_rate": 0.1, "verbosity": -1,
         "tree_learner": "voting"}
    trees = int(os.environ.get("TREES", 10))
    ds = lgb.Dataset(X, y, params=p)
    _, dt = _train(p, ds, trees)
    _emit("sparse_voting_efb", trees, dt, f", {n}x{f} 98.75%-sparse",
          config={**p, "rows": n, "features": f})


ALL = {
    "higgs": lambda: bench_higgs("serial"),
    "higgs_dp": lambda: bench_higgs("data"),
    "ranking": bench_ranking,
    "multiclass": bench_multiclass,
    "sparse": bench_sparse,
}


def main():
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()
    from lightgbm_tpu.utils.log import set_verbosity
    set_verbosity(-1)
    argv = list(sys.argv[1:])
    json_path = None
    telemetry_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            sys.exit("usage: run.py [configs...] --json OUT.json "
                     "[--telemetry OUT.json]")
        json_path = argv[i + 1]
        del argv[i:i + 2]
    if "--telemetry" in argv:
        i = argv.index("--telemetry")
        if i + 1 >= len(argv):
            sys.exit("usage: run.py [configs...] --json OUT.json "
                     "[--telemetry OUT.json]")
        telemetry_path = argv[i + 1]
        del argv[i:i + 2]
    which = argv or list(ALL)
    for name in which:
        ALL[name]()
    if telemetry_path:
        # metrics registry + last benched config's TrainRecord (per-phase
        # seconds, hist passes, collective tallies) — the CI artifact
        from lightgbm_tpu.telemetry import write_snapshot
        write_snapshot(telemetry_path)
        print(json.dumps({"written": telemetry_path,
                          "kind": "telemetry-snapshot-v1"}), flush=True)
    if json_path:
        from lightgbm_tpu.utils.backend import default_backend
        record = {
            "schema": "bench-matrix-v1",
            "git_sha": _git_sha(),
            "backend": default_backend(),
            "scale": SCALE,
            "rows": _RECORDS,
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"written": json_path,
                          "configs": len(_RECORDS)}), flush=True)


if __name__ == "__main__":
    main()
