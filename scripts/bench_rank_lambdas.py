"""Micro-benchmark of the lambdarank gradient program on the chip, at the
query layout of ``istella-letor-rank-q8`` (its 23,219 queries, lengths drawn
from ``weights_seed``; labels drawn with the configuration's label shares,
scores standard normal times 2 rounded to 0.01, so that ties occur): the
device time of one ``get_gradients`` call, its ten costliest operations from
a profiler trace, and ``lambda_gap`` against ``chipbench/reference_rank.py``
on 64 sampled whole queries.  ``--root`` takes ``lightgbm_tpu`` from another
checkout (the same layout and inputs), to pair two of them in one chip call.

    python scripts/bench_rank_lambdas.py [--root CHECKOUT] [--reps 10] [--tag NAME]

Prints one JSON line and appends it to ``chiprun_out/rank_lambdas.jsonl``.
Refuses the CPU."""

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.abspath(args.root))
    import numpy as np
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("bench_rank_lambdas measures the chip; not running on "
                         f"{jax.devices()[0].platform}")
    from chipbench import datagen_rank, reference_rank, trace_reduce
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import Metadata
    from lightgbm_tpu.objective.rank import LambdarankNDCG

    cfg = json.load(open(os.path.join(HERE, "chipbench/configs/istella-letor-rank-q8.json")))
    spec = datagen_rank.RankSpec(cfg["data"])
    rng = np.random.default_rng(0)
    label = rng.choice(5, size=spec.rows, p=spec.label_shares).astype(np.float32)
    score = np.round(rng.standard_normal(spec.rows) * 2, 2).astype(np.float32)
    md = Metadata()
    md.set_label(label)
    md.set_group(spec.lengths)
    obj = LambdarankNDCG(Config(dict(cfg["params"], verbosity=-1)))
    obj.init(md, spec.rows)
    s = jnp.asarray(score)
    t = time.perf_counter()
    lam, hess = obj.get_gradients(s)
    jax.block_until_ready(lam)
    first_s = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(args.reps):
        lam, hess = obj.get_gradients(s)
    jax.block_until_ready((lam, hess))
    ms = (time.perf_counter() - t) * 1e3 / args.reps

    trace_dir = os.path.join(HERE, ".chipwork", "rank_lambdas_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        out = obj.get_gradients(s)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    events, _ = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    first = [ev for ev in events if ev[0] == min(ev[0] for ev in events)] if events else []
    top = [[n, round(sec / 3 * 1e3, 3)] for n, sec in trace_reduce.top_ops(first, 10)]

    p = reference_rank.Params(cfg["params"])
    queries = reference_rank.sample_queries(spec, 1, 64, p.truncation)
    rows = reference_rank.query_rows(spec, queries)
    bounds = np.concatenate([[0], np.cumsum(spec.lengths[queries])])
    ref = reference_rank.lambdas(score[rows], label[rows], bounds, p)
    gap = reference_rank.lambda_gap(np.asarray(lam)[rows], np.asarray(hess)[rows], *ref)
    x = np.arange(2, 4098, dtype=np.float64)
    log2_err = float(np.max(np.abs(np.asarray(jnp.log2(jnp.asarray(x, jnp.float32)),
                                              np.float64) / np.log2(x) - 1)))
    line = {"tag": args.tag, "root": os.path.abspath(args.root), "ms_per_call": ms,
            "first_call_s": first_s, "lambda_gap": gap, "log2_rel_err": log2_err,
            "top_ops_ms_per_call": top, "record": obj.record() if hasattr(obj, "record") else {},
            "device": jax.devices()[0].device_kind}
    print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "rank_lambdas.jsonl"), "a") as fh:
        fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
