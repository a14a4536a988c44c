"""Many-models throughput bench: ``train_many`` vs sequential ``train``.

Measures models/sec at 100k x 28 (scaled by ``SCALE``) for a ladder of
batch widths M, against a sequential-train() baseline extrapolated from
``SEQ_SAMPLES`` standalone runs (every train() is independent and the
compiled grower is shared through the grow-fn cache, so per-model
sequential time is constant after the first call).  Emits one
``bench-matrix-v1`` record (``--json out.json``) with a
``speedup_vs_sequential`` column per M — the ISSUE 7 acceptance series.

    JAX_PLATFORMS=cpu SCALE=0.05 python benchmarks/many_models.py \
        --json many_models.json

Defaults to the acceptance geometry (100k x 28, 31 leaves, 20 rounds,
M up to 64); SCALE shrinks rows for CI smoke runs.

The lifted-variant rungs (PR 20) repeat the ladder per boosting family
that used to be a structural fallback — goss / dart / multiclass /
ranking — on 1k-row models at ``FAM_M_LADDER`` widths, each against its
own sequential baseline (rows ``many_models_{family}_M{M}``).  Set
``FAMILIES=`` to skip them.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCALE = float(os.environ.get("SCALE", 1.0))
ROUNDS = int(os.environ.get("ROUNDS", 20))
SEQ_SAMPLES = int(os.environ.get("SEQ_SAMPLES", 3))
M_LADDER = tuple(int(m) for m in
                 os.environ.get("M_LADDER", "1,8,16,64").split(","))
FAM_M_LADDER = tuple(int(m) for m in
                     os.environ.get("FAM_M_LADDER", "8,32").split(","))
FAMILIES = tuple(f for f in
                 os.environ.get("FAMILIES",
                                "goss,dart,multiclass,ranking").split(",")
                 if f)
FAM_ROUNDS = int(os.environ.get("FAM_ROUNDS", 20))
FAM_N = int(os.environ.get("FAM_N", 1000))   # acceptance: 1k-row models

N, F = max(1000, int(100_000 * SCALE)), 28
PARAMS = {"objective": "regression", "num_leaves": 31,
          "learning_rate": 0.1, "verbosity": -1}

# Each lifted family sweeps only HOST_SWEEP knobs so the whole ladder
# stays one batched program (num_groups == 1 asserted below).
FAMILY_SPECS = {
    "goss": {"params": {"objective": "binary", "boosting": "goss",
                        "learning_rate": 0.5, "num_leaves": 31,
                        "verbosity": -1},
             "task": "binary",
             "variant": lambda i: {"top_rate": 0.15 + 0.01 * (i % 8),
                                   "other_rate": 0.05 + 0.01 * (i % 5)}},
    "dart": {"params": {"objective": "binary", "boosting": "dart",
                        "drop_rate": 0.1, "num_leaves": 31,
                        "learning_rate": 0.1, "verbosity": -1},
             "task": "binary",
             "variant": lambda i: {"drop_seed": 100 + i,
                                   "drop_rate": 0.05 + 0.02 * (i % 5)}},
    "multiclass": {"params": {"objective": "multiclass", "num_class": 3,
                              "num_leaves": 31, "learning_rate": 0.1,
                              "verbosity": -1},
                   "task": "mc",
                   "variant": lambda i: {"lambda_l2": 0.1 * i}},
    "ranking": {"params": {"objective": "lambdarank", "num_leaves": 31,
                           "learning_rate": 0.1, "verbosity": -1},
                "task": "rank",
                "variant": lambda i: {"lambda_l2": 0.1 * i}},
}


def _family_data(task, n, f=F, seed=1):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    raw = X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n)
    groups = None
    if task == "binary":
        y = (raw > 0).astype(np.float64)
    elif task == "mc":
        y = np.digitize(raw, [-0.5, 0.5]).astype(np.float64)
    else:                                      # rank: graded relevance
        y = np.clip(np.round(raw + 2), 0, 4).astype(np.float64)
        groups = [30] * (n // 30)
        groups[-1] += n - sum(groups)
    return X, y, groups


def _git_sha():
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or None
    except Exception:
        return None


def main(argv):
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        json_path = argv[i + 1]
        del argv[i:i + 2]

    import lightgbm_tpu as lgb
    from lightgbm_tpu.multitrain import train_many

    rng = np.random.RandomState(0)
    X = rng.randn(N, F).astype(np.float32)
    y = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.1 * rng.randn(N)
    ds = lgb.Dataset(X, y)
    ds.construct(lgb.Config(PARAMS))

    def variant(i):
        return {"lambda_l2": 0.1 * i}

    # warm both compile paths out of the timed regions
    lgb.train({**PARAMS, **variant(990)}, ds, 2)
    train_many(PARAMS, ds, num_boost_round=2,
               variants=[variant(991), variant(992)])

    t0 = time.time()
    for i in range(SEQ_SAMPLES):
        lgb.train({**PARAMS, **variant(900 + i)}, ds, ROUNDS)
    seq_per_model = (time.time() - t0) / SEQ_SAMPLES
    seq_models_per_sec = 1.0 / seq_per_model
    print(json.dumps({"metric": "sequential_models_per_sec",
                      "value": round(seq_models_per_sec, 4),
                      "rows": N, "features": F, "rounds": ROUNDS}),
          flush=True)

    rows = []
    for M in M_LADDER:
        t0 = time.time()
        mb = train_many(PARAMS, ds, num_boost_round=ROUNDS,
                        variants=[variant(i) for i in range(M)])
        dt = time.time() - t0
        assert len(mb) == M and not mb.fallback_indices
        mps = M / dt
        speedup = mps / seq_models_per_sec
        rec = {"metric": f"train_many_models_per_sec (M={M})",
               "value": round(mps, 4),
               "speedup_vs_sequential": round(speedup, 3),
               "batch_seconds": round(dt, 2),
               "rows": N, "features": F, "rounds": ROUNDS,
               "num_leaves": PARAMS["num_leaves"]}
        print(json.dumps(rec), flush=True)
        rows.append({"name": f"many_models_M{M}",
                     "config": {**PARAMS, "M": M, "rounds": ROUNDS,
                                "rows": N, "features": F},
                     "models_per_sec": round(mps, 4),
                     "speedup_vs_sequential": round(speedup, 3)})

    for fam in FAMILIES:
        spec = FAMILY_SPECS[fam]
        fparams, fvariant = spec["params"], spec["variant"]
        Xf, yf, groups = _family_data(spec["task"], FAM_N)
        fds = lgb.Dataset(Xf, yf, group=groups)
        fds.construct(lgb.Config(fparams))

        lgb.train({**fparams, **fvariant(990)}, fds, 2)
        train_many(fparams, fds, num_boost_round=2,
                   variants=[fvariant(991), fvariant(992)])

        t0 = time.time()
        for i in range(SEQ_SAMPLES):
            lgb.train({**fparams, **fvariant(900 + i)}, fds, FAM_ROUNDS)
        fam_seq_per_sec = SEQ_SAMPLES / (time.time() - t0)

        for M in FAM_M_LADDER:
            fvars = [fvariant(i) for i in range(M)]
            # warm this batch width's compile out of the timed region:
            # at 1k rows a fresh M-wide grower compile would dominate
            # the 20-round run (the sequential baseline's compile is
            # equally cached by its warm-up above)
            train_many(fparams, fds, num_boost_round=2, variants=fvars)
            t0 = time.time()
            mb = train_many(fparams, fds, num_boost_round=FAM_ROUNDS,
                            variants=fvars)
            dt = time.time() - t0
            assert len(mb) == M and not mb.fallback_indices, \
                f"{fam}: lifted family fell back ({mb.fallback_indices})"
            assert mb.num_groups == 1, \
                f"{fam}: sweep split into {mb.num_groups} batches"
            mps = M / dt
            speedup = mps / fam_seq_per_sec
            rec = {"metric": f"train_many_{fam}_models_per_sec (M={M})",
                   "value": round(mps, 4),
                   "speedup_vs_sequential": round(speedup, 3),
                   "batch_seconds": round(dt, 2),
                   "rows": FAM_N, "features": F, "rounds": FAM_ROUNDS}
            print(json.dumps(rec), flush=True)
            rows.append({"name": f"many_models_{fam}_M{M}",
                         "config": {**fparams, "M": M,
                                    "rounds": FAM_ROUNDS,
                                    "rows": FAM_N, "features": F},
                         "models_per_sec": round(mps, 4),
                         "speedup_vs_sequential": round(speedup, 3)})

    if json_path:
        from lightgbm_tpu.utils.backend import default_backend
        record = {
            "schema": "bench-matrix-v1",
            "git_sha": _git_sha(),
            "backend": default_backend(),
            "scale": SCALE,
            "sequential_models_per_sec": round(seq_models_per_sec, 4),
            "rows": rows,
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"written": json_path, "ladder": list(M_LADDER)}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
