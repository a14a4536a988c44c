"""Multi-host smoke: 2 real ``jax.distributed`` CPU processes (gloo)
train the quantized DP wave path on pre-partitioned row shards and the
resulting MODEL TEXT must be byte-identical to a single-process 2-device
run of the same job — the pod data path's bit-identity gate (blocking in
CI next to the multichip dryrun).

Why byte-identity is achievable and therefore demanded: the W=2 world is
the same in both layouts (2 procs x 1 device vs 1 proc x 2 devices), the
row->shard split is the same contiguous halves, quantized histograms
psum in int32 (order-insensitive), stochastic rounding is off, and
distributed bin finding merges per-rank sketches that cover every row
(bin_construct_sample_cnt >> N) into the same summaries the in-core
construct sees.  Any byte of drift means a real divergence in binning,
histogram merging, split selection or text serialization.

A second phase repeats the run through the streamed ingest path — each
rank feeds ONLY its shard through a ChunkSource and binning rides the
mergeable-sketch wire format — and must match the same baseline text.

Usage: python scripts/multihost_smoke.py [--out multihost-smoke.json]
(--worker/--baseline are internal re-invocation modes).

CPU ONLY — never run this on the chip.  The launcher spawns three
children and pins every one of them to ``JAX_PLATFORMS=cpu``: a chip
belongs to one process at a time, so a spawned child that needed it
would fail or hang.  The chip's own smoke is the single-process
``chip_smoke.py`` at the repo root.
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N, F, ROUNDS = 600, 6, 4

# pre_partition is set in BOTH layouts (inert single-process) so the
# model-text parameters block is identical byte-for-byte
PARAMS = {
    "objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
    "verbosity": -1, "tree_learner": "data", "tree_grow_mode": "wave",
    "use_quantized_grad": True, "stochastic_rounding": False,
    "quant_train_renew_leaf": True, "pre_partition": True,
}


def _make_data():
    import numpy as np
    rng = np.random.RandomState(31)
    X = rng.randn(N, F)
    y = ((X[:, 0] + 0.5 * X[:, 1] - 0.2 * X[:, 2] ** 2) > 0).astype(float)
    return X, y


def _set_cpu_devices(k):
    import jax
    jax.config.update("jax_num_cpu_devices", k)

def _run_worker(rank: int, port: str, outdir: str) -> int:
    _set_cpu_devices(1)           # 2 procs x 1 device = W=2
    import lightgbm_tpu as lgb
    lgb.distributed.init(coordinator_address="127.0.0.1:" + port,
                         num_processes=2, process_id=rank)
    from lightgbm_tpu.utils.log import set_verbosity
    set_verbosity(-1)
    X, y = _make_data()
    lo, hi = (0, N // 2) if rank == 0 else (N // 2, N)

    bst = lgb.train(dict(PARAMS), lgb.Dataset(X[lo:hi], y[lo:hi]), ROUNDS)
    with open(os.path.join(outdir, f"model_dist_{rank}.txt"), "w") as fh:
        fh.write(bst.model_to_string())

    # streamed phase: this rank's shard arrives chunk-by-chunk through
    # its own ChunkSource; sketches merge over the allgather wire
    from lightgbm_tpu.ingest.source import ArraySource
    from lightgbm_tpu.ingest.stream import StreamedDataset
    sd = StreamedDataset(ArraySource(X[lo:hi], y[lo:hi], chunk_rows=256),
                         params=dict(PARAMS))
    bst2 = lgb.train(dict(PARAMS), sd, ROUNDS)
    with open(os.path.join(outdir, f"model_stream_{rank}.txt"), "w") as fh:
        fh.write(bst2.model_to_string())
    return 0


def _run_baseline(outdir: str) -> int:
    _set_cpu_devices(2)           # 1 proc x 2 devices = same W=2 world
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.log import set_verbosity
    set_verbosity(-1)
    X, y = _make_data()
    bst = lgb.train(dict(PARAMS), lgb.Dataset(X, y), ROUNDS)
    with open(os.path.join(outdir, "model_single.txt"), "w") as fh:
        fh.write(bst.model_to_string())
    return 0


def _free_port() -> str:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return str(port)


def _launch(outdir: str) -> dict:
    # children never touch an accelerator (see the module docstring)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    me = os.path.abspath(__file__)
    port = _free_port()
    t0 = time.perf_counter()
    procs = [subprocess.Popen(
        [sys.executable, me, "--worker", str(r), "--port", port,
         "--dir", outdir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    procs.append(subprocess.Popen(
        [sys.executable, me, "--baseline", "--dir", outdir],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs, rcs = [], []
    for p in procs:
        try:
            out = p.communicate(timeout=600)[0].decode()
        except subprocess.TimeoutExpired:
            p.kill()
            out = p.communicate()[0].decode() + "\n<timeout>"
        outs.append(out)
        rcs.append(p.returncode)
    rec = {"schema": "multihost-smoke-v1", "ok": False,
           "world": {"processes": 2, "devices_per_process": 1},
           "launch_seconds": round(time.perf_counter() - t0, 2),
           "returncodes": rcs}
    if any(rc != 0 for rc in rcs):
        rec["error"] = "\n===\n".join(o[-2500:] for o in outs)
        return rec

    def read(name):
        with open(os.path.join(outdir, name), "rb") as fh:
            return fh.read()

    single = read("model_single.txt")
    checks = {}
    for tag in ("dist", "stream"):
        m0, m1 = read(f"model_{tag}_0.txt"), read(f"model_{tag}_1.txt")
        checks[f"{tag}_ranks_identical"] = m0 == m1
        checks[f"{tag}_matches_single_process"] = m0 == single
    rec["model_text_bytes"] = len(single)
    rec["bit_identical"] = checks
    rec["ok"] = all(checks.values())
    if not rec["ok"]:
        # first divergent line per failing pair, for the CI log
        import difflib
        diffs = {}
        for tag in ("dist", "stream"):
            if not checks[f"{tag}_matches_single_process"]:
                a = read(f"model_{tag}_0.txt").decode().splitlines()
                b = single.decode().splitlines()
                diffs[tag] = [ln for ln in difflib.unified_diff(
                    a, b, "distributed", "single", lineterm="", n=0)][:12]
        rec["first_divergence"] = diffs
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="multihost-smoke.json")
    ap.add_argument("--worker", type=int, default=None)
    ap.add_argument("--baseline", action="store_true")
    ap.add_argument("--port", default=None)
    ap.add_argument("--dir", default=None)
    ns = ap.parse_args()
    if ns.worker is not None:
        return _run_worker(ns.worker, ns.port, ns.dir)
    if ns.baseline:
        return _run_baseline(ns.dir)

    import tempfile
    with tempfile.TemporaryDirectory() as td:
        rec = _launch(td)
    with open(ns.out, "w") as fh:
        json.dump(rec, fh, indent=2, default=str)
    print(json.dumps({k: rec.get(k) for k in
                      ("ok", "launch_seconds", "bit_identical")}))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
