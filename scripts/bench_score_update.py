"""Micro-benchmark of the training-set score update (ISSUE 35), on the
chip: ``score + leaf_value[row_leaf]`` as the XLA gather, as the streaming
select kernel (``ops/histogram_pallas.py`` ``score_update_pallas``) and as
two plain XLA selects, at the benchmark cells' row counts.

    python scripts/bench_score_update.py [--rows ...] [--leaves ...]

Prints one JSON line per measurement and writes them all to
``chiprun_out/score_update.json``.  Times are host clock around
``block_until_ready`` over ``--reps`` calls after one warm-up, each call
donating the score the one before returned; every lowering's scores are
held to the gather's bits.  Refuses the CPU."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

from lightgbm_tpu.models import gbdt
from lightgbm_tpu.ops import histogram_pallas as hp


def xla_chain(score, rl, lv, shrinkage):
    """``acc = where(rl == l, lv[l], acc)`` left to XLA's fusion."""
    n, = score.shape
    rl = rl[:n]
    acc = jnp.full((n,), lv[0], jnp.float32)
    for l in range(1, lv.shape[0]):
        acc = jnp.where(rl == l, lv[l], acc)
    return score + acc


def xla_sum(score, rl, lv, shrinkage):
    """Leaves on the major axis, rows on the lanes; one non-zero term."""
    n, = score.shape
    leaf = jnp.arange(lv.shape[0], dtype=jnp.int32)
    return score + jnp.where(rl[None, :n] == leaf[:, None], lv[:, None],
                             0.0).sum(0)


def timed(fn, score, rl, lv, reps):
    score = jax.block_until_ready(fn(score, rl, lv, 1.0))
    t = time.perf_counter()
    for _ in range(reps):
        score = fn(score, rl, lv, 1.0)
    jax.block_until_ready(score)
    return 1e3 * (time.perf_counter() - t) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="21250000,45840617")
    ap.add_argument("--leaves", default="255,1023,4095")
    ap.add_argument("--tilings", default="65536:8192:64",
                    help="comma list of kr:lanes:group for the kernel")
    ap.add_argument("--xla-leaves", type=int, default=255,
                    help="largest table the plain XLA selects are timed at")
    ap.add_argument("--reps", type=int, default=10)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("bench_score_update measures the chip only")
    lines = []

    def emit(**kw):
        kw["device"] = jax.devices()[0].device_kind
        lines.append(kw)
        print(json.dumps(kw), flush=True)

    def donated(impl):
        return jax.jit(impl, donate_argnums=gbdt.SCORE_DONATE_ARGNUMS)

    for n in (int(v) for v in a.rows.split(",")):
        n_pad = hp.pad_rows(n)
        for leaves in (int(v) for v in a.leaves.split(",")):
            rng = np.random.RandomState(leaves)
            rl = jnp.asarray(rng.randint(0, leaves, n_pad).astype(np.int32))
            lv = jnp.asarray(rng.randn(leaves).astype(np.float32))
            score0 = rng.randn(n).astype(np.float32)
            want = np.asarray(gbdt._update_score_by_leaf(
                jnp.asarray(score0), rl[:n], lv, 1.0))

            def measure(name, fn, rl_arg, **kw):
                t = time.perf_counter()
                got = np.asarray(fn(jnp.asarray(score0), rl_arg, lv, 1.0))
                first_s = time.perf_counter() - t
                emit(lowering=name, rows=n, leaves=leaves,
                     ms=timed(fn, jnp.asarray(score0), rl_arg, lv, a.reps),
                     first_call_s=first_s,
                     bits_equal=bool(np.array_equal(
                         got.view(np.uint32), want.view(np.uint32))), **kw)

            measure("gather", gbdt._update_score_by_leaf_donated, rl[:n])
            for tiling in a.tilings.split(","):
                hp._SU_KR, hp._SU_LANES, hp._SU_GROUP = (
                    int(v) for v in tiling.split(":"))
                jax.clear_caches()  # the constants are no part of jit's key
                measure("select", donated(gbdt._score_select_impl), rl,
                        tiling=tiling)
            if leaves <= a.xla_leaves:
                measure("xla_chain", donated(xla_chain), rl)
                measure("xla_sum", donated(xla_sum), rl)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/score_update.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
