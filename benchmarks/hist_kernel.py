"""Histogram-kernel micro-benchmark: the impl/variant x B x row_block
ladder, in bench-matrix-v1 records.

Promoted from scripts/bench_hist.py (which now delegates here).  Each
rung measures ONE full histogram build — the op that dominates training
(PERF.md) — and reports builds/s plus effective streamed GB/s
(bins + weight rows in, histogram out).  Variants:

* ``segment`` / ``onehot`` / ``packed4`` — the XLA formulations
  (ops/histogram.py); ``packed4`` is the joint-nibble scatter that
  halves scatter volume for max_bin<=16 data (B=16 rungs only).
* ``pallas`` / ``pallas:blockspec`` / ``pallas:packed4`` — the Pallas
  kernel pipelines (ops/histogram_pallas.py): DMA double-buffered
  streaming (default), the v1 BlockSpec fetch, and the DMA + 4-bit
  packed-bin layout.  Off-TPU these run the INTERPRETER (a correctness
  proxy, ~1000x slow) and are capped at PALLAS_ROWS rows — their
  builds/s are recorded with ``interpreted: true`` and excluded from
  speedup claims.

    JAX_PLATFORMS=cpu SCALE=1.0 python benchmarks/hist_kernel.py \
        --json hist-kernel.json

Env knobs: SCALE (rows multiplier), ROWS (default 1<<20), FEATURES (28),
B_LADDER ("16,64,255"), ROW_BLOCKS ("4096"), REPS (3),
PALLAS_ROWS (16384 off-TPU), SKIP_PALLAS=1 to drop the interpret rungs.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCALE = float(os.environ.get("SCALE", 1.0))
ROWS = max(4096, int(int(os.environ.get("ROWS", 1 << 20)) * SCALE) // 4096 * 4096)
FEATURES = int(os.environ.get("FEATURES", 28))
B_LADDER = tuple(int(b) for b in
                 os.environ.get("B_LADDER", "16,64,255").split(","))
ROW_BLOCKS = tuple(int(r) for r in
                   os.environ.get("ROW_BLOCKS", "4096").split(","))
REPS = int(os.environ.get("REPS", 3))
PALLAS_ROWS = int(os.environ.get("PALLAS_ROWS", 16384))
SKIP_PALLAS = os.environ.get("SKIP_PALLAS", "") == "1"


def _git_sha():
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or None
    except Exception:
        return None


def _timeit(fn, reps):
    import jax
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def main(argv):
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        json_path = argv[i + 1]
        del argv[i:i + 2]

    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import build_histogram
    from lightgbm_tpu.ops.histogram_pallas import (build_histogram_pallas,
                                                   pack_bins4, pad_rows)
    from lightgbm_tpu.utils.backend import default_backend

    backend = default_backend()
    on_tpu = backend == "tpu"
    pallas_rows = ROWS if on_tpu else min(ROWS, max(4096, PALLAS_ROWS))
    rng = np.random.RandomState(0)
    rows_out = []
    baseline_bps = {}   # (B, rows) -> builds/s of the baseline impl

    for B in B_LADDER:
        bins = rng.randint(0, B, (ROWS, FEATURES)).astype(np.uint8)
        grad = rng.randn(ROWS).astype(np.float32)
        hess = np.abs(rng.randn(ROWS)).astype(np.float32)
        mask = (rng.rand(ROWS) < 0.8).astype(np.float32)
        bins_d = jnp.asarray(bins)
        g, h, m = map(jnp.asarray, (grad, hess, mask))

        xla_impls = ["segment", "onehot"] + (["packed4"] if B <= 16 else [])
        baseline_impl = "onehot" if on_tpu else "segment"
        for impl in xla_impls:
            def run(impl=impl):
                return build_histogram(bins_d, g, h, m, num_bins=B,
                                       impl=impl)
            dt = _timeit(run, REPS)
            bps = 1.0 / dt
            streamed = ROWS * FEATURES + ROWS * 12 + FEATURES * B * 12
            if impl == baseline_impl:
                baseline_bps[(B, ROWS)] = bps
            rows_out.append({
                "name": f"hist_{impl}_B{B}",
                "config": {"impl": impl, "num_bins": B, "rows": ROWS,
                           "features": FEATURES, "row_block": 0},
                "builds_per_sec": round(bps, 4),
                "gbytes_per_sec": round(streamed * bps / 1e9, 3),
                "interpreted": False,
            })
            print(json.dumps(rows_out[-1]), flush=True)

        if SKIP_PALLAS:
            continue
        n_p = pad_rows(pallas_rows)
        bins_t = jnp.asarray(
            np.pad(bins[:pallas_rows], ((0, n_p - pallas_rows),
                                        (0, 0))).T.copy())
        gp = jnp.asarray(np.pad(grad[:pallas_rows], (0, n_p - pallas_rows)))
        hp = jnp.asarray(np.pad(hess[:pallas_rows], (0, n_p - pallas_rows)))
        mp = jnp.asarray(np.pad(mask[:pallas_rows], (0, n_p - pallas_rows)))
        pk = pack_bins4(bins_t) if B <= 16 else None
        variants = [("pallas", dict(pipeline="dma")),
                    ("pallas:blockspec", dict(pipeline="blockspec"))]
        if B <= 16:
            variants.append(("pallas:packed4", dict(bins_packed=True)))
        for rb in ROW_BLOCKS:
            if n_p % rb:
                continue
            for name, kw in variants:
                src = pk if kw.get("bins_packed") else bins_t

                def run(src=src, kw=kw, rb=rb):
                    return build_histogram_pallas(src, gp, hp, mp,
                                                  num_bins=B, row_block=rb,
                                                  **kw)
                try:
                    dt = _timeit(run, REPS)
                except Exception as e:  # noqa: BLE001 — record the failure
                    rows_out.append({
                        "name": f"hist_{name}_B{B}_rb{rb}",
                        "config": {"impl": name, "num_bins": B,
                                   "rows": n_p, "features": FEATURES,
                                   "row_block": rb},
                        "error": f"{type(e).__name__}: {e}"[:200],
                    })
                    continue
                bps = 1.0 / dt
                bin_bytes = FEATURES * (n_p // 2 if kw.get("bins_packed")
                                        else n_p)
                streamed = bin_bytes + n_p * 16 + FEATURES * B * 12
                rows_out.append({
                    "name": f"hist_{name}_B{B}_rb{rb}",
                    "config": {"impl": name, "num_bins": B, "rows": n_p,
                               "features": FEATURES, "row_block": rb},
                    "builds_per_sec": round(bps, 4),
                    "gbytes_per_sec": round(streamed * bps / 1e9, 3),
                    "interpreted": not on_tpu,
                })
                print(json.dumps(rows_out[-1]), flush=True)

    # speedups vs the backend's default impl at the same (B, rows) —
    # interpret-mode pallas rungs are correctness proxies, not claims
    for r in rows_out:
        key = (r["config"]["num_bins"], r["config"]["rows"])
        base = baseline_bps.get(key)
        if base and not r.get("interpreted") and "builds_per_sec" in r:
            r["speedup_vs_baseline"] = round(r["builds_per_sec"] / base, 3)

    if json_path:
        record = {
            "schema": "bench-matrix-v1",
            "bench": "hist_kernel",
            "git_sha": _git_sha(),
            "backend": backend,
            "scale": SCALE,
            "rows": rows_out,
        }
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
        print(json.dumps({"written": json_path,
                          "rungs": len(rows_out)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
