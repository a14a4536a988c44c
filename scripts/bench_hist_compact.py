"""Micro-benchmark of the row compaction in front of the DMA leaf kernels
(ISSUE 31), on the chip: one pass at the benchmark cells' shape, dense
against compacted, at several shares of active rows.

    python scripts/bench_hist_compact.py [--kinds q8,bf16] [--shares ...]

Prints one JSON line per measurement and writes them all to
``chiprun_out/hist_compact.json``.  Times are host clock around
``block_until_ready`` over ``--reps`` calls after one warm-up; ``plan`` is
the XLA work on ``ch``, ``compact`` plan + compaction kernel, ``pass``
the whole compacted pass.  Refuses the CPU."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import histogram_pallas as hp


def timed(fn, *args, reps):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / reps, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=21_250_048)
    ap.add_argument("--features", type=int, default=67)
    ap.add_argument("--kinds", default="q8,bf16")
    ap.add_argument("--shares", default="1.0,0.5,0.3,0.25,0.1,0.02")
    ap.add_argument("--tilings", default="512:8192",
                    help="comma list of sub:kb")
    ap.add_argument("--reps", type=int, default=5)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("bench_hist_compact measures the chip only")
    n, f = a.rows, a.features
    key = jax.random.PRNGKey(0)
    bins = jax.random.randint(key, (f, n), 0, 255, jnp.int32).astype(jnp.uint8)
    lines = []

    def emit(**kw):
        kw["device"] = jax.devices()[0].device_kind
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    for kind in a.kinds.split(","):
        if kind == "q8":
            build, nch = hp.build_histogram_pallas_leaves_q8, 42
            w = jax.random.randint(key, (8, n), -127, 128,
                                   jnp.int32).astype(jnp.int8).at[3:].set(0)
        else:
            build, nch = hp.build_histogram_pallas_leaves, 25
            g = jax.random.normal(key, (n,), jnp.float32)
            w = hp.pack_weights8(g, jnp.abs(g), jnp.ones((n,), jnp.float32))
        dense = jax.jit(lambda b, w_, c: build(b, w_, c, num_bins=255,
                                              pipeline="dma"))
        for tiling in a.tilings.split(","):
            hp._CP_SUB, hp._CP_KB = (int(v) for v in tiling.split(":"))
            hp._CP_VMEM = 16 << 20
            jax.clear_caches()
            comp = jax.jit(lambda b, w_, c: build(
                b, w_, c, num_bins=255, pipeline="dma", compact=True))
            kb = hp._compact_block(n, 96)
            plan = jax.jit(lambda c: hp._compact_plan(
                c.astype(jnp.int32), kb=kb, kr=4096, interpret=False))

            def only(b, w_, c):
                bp = jnp.pad(b, ((0, 96 - f), (0, 0)))
                o = hp._compact_rows_dma(
                    bp, w_, c.astype(jnp.int32).reshape(1, n), fc=72,
                    kr=4096, interpret=False)
                return o[3], o[0][0, :8], o[1][0, :8], o[2][0, :8]
            only = jax.jit(only)
            for share in (float(v) for v in a.shares.split(",")):
                u = jax.random.uniform(jax.random.PRNGKey(1), (n,))
                ch = jnp.where(u < share, jax.random.randint(
                    jax.random.PRNGKey(2), (n,), 0, nch), -1).astype(jnp.int8)
                t_dense, hd = timed(dense, bins, w, ch, reps=a.reps)
                t_plan, _ = timed(plan, ch, reps=a.reps)
                t_comp, _ = timed(only, bins, w, ch, reps=a.reps)
                t_pass, (hc, rows) = timed(comp, bins, w, ch, reps=a.reps)
                if kind == "q8":
                    gap = int(jnp.max(jnp.abs(hd - hc)))
                else:
                    gap = float(jnp.max(jnp.abs(hd - hc)) /
                                jnp.maximum(jnp.max(jnp.abs(hd)), 1e-30))
                emit(kind=kind, tiling=tiling, share=share, rows=n,
                     features=f, dense_ms=t_dense, plan_ms=t_plan,
                     compact_ms=t_comp, pass_ms=t_pass,
                     rows_contracted=int(rows[0]), gap=gap)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/hist_compact.json", "w") as fh:
        json.dump(lines, fh, indent=1)


if __name__ == "__main__":
    main()
