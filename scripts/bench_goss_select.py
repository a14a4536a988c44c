"""Micro-benchmark of the GOSS draw (ISSUE 32), on the chip, at the benchmark
cell's rows: the exact k-th largest of ``|g*h|`` by ``jnp.sort`` against the
bitwise select the sampler uses (``models/gbdt.py`` ``_kth_largest_u32``),
and the whole jitted sampler.

    python scripts/bench_goss_select.py [--rows 21250000] [--reps 5]

Prints one JSON line per measurement and writes them all to
``chiprun_out/goss_select.json``.  Times are host clock around
``block_until_ready`` over ``--reps`` calls after one warm-up.  Refuses the
CPU."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from lightgbm_tpu.models import gbdt


def timed(fn, *args, reps):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / reps, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=21_250_000)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench_goss_select measures the chip. Not running.")
    n, a, b = args.rows, 0.2, 0.1
    k = max(1, int(n * a))
    kg, kh = jax.random.split(jax.random.PRNGKey(7))
    p = jax.nn.sigmoid(1.5 * jax.random.normal(kg, (n,)))
    y = (jax.random.uniform(kh, (n,)) < 0.5).astype(jnp.float32)
    grad, hess = p - y, p * (1.0 - p)

    def bits(g, h):
        return jax.lax.bitcast_convert_type(jnp.abs(g * h), jnp.uint32)

    by_sort = jax.jit(lambda g, h: jnp.sort(bits(g, h))[n - k])
    by_select = jax.jit(lambda g, h: gbdt._kth_largest_u32(bits(g, h), k))
    sample = lambda g, h: gbdt.goss_sample(g, h, 11, top_rate=a, other_rate=b, bagging_seed=3)

    out = []
    ms_sort, t_sort = timed(by_sort, grad, hess, reps=args.reps)
    ms_sel, t_sel = timed(by_select, grad, hess, reps=args.reps)
    ms_all, drawn = timed(sample, grad, hess, reps=args.reps)
    cls = drawn[0]
    top = int(jnp.sum(cls == gbdt.GOSS_TOP))
    rest = int(jnp.sum(cls == gbdt.GOSS_REST))
    for rec in ({"what": "kth_by_sort", "ms": ms_sort, "bits": int(t_sort)},
                {"what": "kth_by_select", "ms": ms_sel, "bits": int(t_sel),
                 "equal_to_sort": bool(t_sort == t_sel)},
                {"what": "goss_sample", "ms": ms_all, "top": top, "top_share": top / n,
                 "rest_rate": rest / (n - top), "sampled_rows": int(drawn[4])}):
        rec.update(rows=n, device=jax.devices()[0].device_kind)
        print(json.dumps(rec), flush=True)
        out.append(rec)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/goss_select.json", "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
