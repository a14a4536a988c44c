"""True multi-PROCESS distributed training on localhost (the reference's
test_dask.py pattern: an in-process multi-worker cluster per test run,
each worker doing a real network init, results asserted ≈ serial).

Here each worker is a separate OS process running the same SPMD driver:
``lightgbm_tpu.distributed.init`` forms the JAX multi-process runtime
(gloo collectives on CPU), the data-parallel learner's mesh spans both
processes' devices, and the resulting model must match single-process
training exactly.  Every non-slow suite shares ONE 2-process world (a
module-scoped fixture): each extra worker-pair launch costs a full jax
import + gloo init on CI, so the data-learner, wave, voting and
pre-partition suites all train inside the same pair of processes."""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one world, every non-slow cross-process suite: data learner (masked
# grower), quantized wave grower, voting-parallel learner, then the
# pre_partition shard suites (dense binary, sparse, linear trees)
_WORKER = textwrap.dedent("""
    import sys
    rank = int(sys.argv[1]); port = sys.argv[2]; outdir = sys.argv[3]
    sys.path.insert(0, {repo!r})
    import os
    import jax
    jax.config.update("jax_num_cpu_devices", 2)
    import lightgbm_tpu as lgb
    lgb.distributed.init(coordinator_address="127.0.0.1:" + port,
                         num_processes=2, process_id=rank)
    import numpy as np
    import scipy.sparse as sp
    from lightgbm_tpu.utils.log import set_verbosity
    set_verbosity(-1)
    rng = np.random.RandomState(11)
    n = 700
    X = rng.randn(n, 6)
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 2] ** 2 * 0.2) > 0).astype(float)
    P = {{"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1, "tree_learner": "data"}}
    # the wave grower (quantized, deterministic rounding) cross-process
    # before the masked-grower run
    PW = dict(P, tree_grow_mode="wave", use_quantized_grad=True,
              stochastic_rounding=False, quant_train_renew_leaf=True)
    bw = lgb.train(PW, lgb.Dataset(X, y), 3)
    np.save(f"{{outdir}}/wpred_{{rank}}.npy", bw.predict(X))
    # the voting-parallel learner in the SAME world
    bv = lgb.train(dict(P, tree_learner="voting"), lgb.Dataset(X, y), 5)
    np.save(f"{{outdir}}/vpred_{{rank}}.npy", bv.predict(X))
    bst = lgb.train(P, lgb.Dataset(X, y), 5)
    np.save(f"{{outdir}}/pred_{{rank}}.npy", bst.predict(X))

    # dense pre_partition: disjoint binary shards must reproduce
    # full-data training
    lo, hi = (0, 350) if rank == 0 else (350, 700)
    PP = dict(P, pre_partition=True)
    bst = lgb.train(PP, lgb.Dataset(X[lo:hi], y[lo:hi]), 5)
    np.save(f"{{outdir}}/ppred_{{rank}}.npy", bst.predict(X))

    # sparse shards + linear trees, still the same 2-process world
    rng = np.random.RandomState(23)
    n = 800
    X = rng.randn(n, 6)
    y = (X[:, 0] * 2 - X[:, 1] + 0.3 * rng.randn(n))
    lo, hi = (0, 400) if rank == 0 else (400, 800)
    PR = {{"objective": "regression", "num_leaves": 7, "min_data_in_leaf": 5,
           "verbosity": -1, "tree_learner": "data", "pre_partition": True}}
    Xs = X.copy(); Xs[np.abs(Xs) < 0.6] = 0.0
    local = sp.csr_matrix(Xs[lo:hi])
    bst = lgb.train(PR, lgb.Dataset(local, y[lo:hi]), 5)
    np.save(f"{{outdir}}/spred_{{rank}}.npy", bst.predict(Xs))
    PL = dict(PR, linear_tree=True)
    bst = lgb.train(PL, lgb.Dataset(X[lo:hi], y[lo:hi]), 5)
    np.save(f"{{outdir}}/lpred_{{rank}}.npy", bst.predict(X))
""")

# feature-parallel only (skipped until the env's jax grows shard_map) —
# kept out of the shared world so the shared launch never depends on it
_WORKER_FP = textwrap.dedent("""
    import sys
    rank = int(sys.argv[1]); port = sys.argv[2]; outdir = sys.argv[3]
    sys.path.insert(0, {repo!r})
    import os
    import jax
    jax.config.update("jax_num_cpu_devices", 2)
    import lightgbm_tpu as lgb
    lgb.distributed.init(coordinator_address="127.0.0.1:" + port,
                         num_processes=2, process_id=rank)
    import numpy as np
    from lightgbm_tpu.utils.log import set_verbosity
    set_verbosity(-1)
    rng = np.random.RandomState(11)
    n = 700
    X = rng.randn(n, 6)
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 2] ** 2 * 0.2) > 0).astype(float)
    P = {{"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1, "tree_learner": "feature"}}
    bst = lgb.train(P, lgb.Dataset(X, y), 5)
    np.save(f"{{outdir}}/fpred_{{rank}}.npy", bst.predict(X))
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_pair(script_body, outdir):
    script = os.path.join(str(outdir), "worker.py")
    with open(script, "w") as fh:
        fh.write(script_body.format(repo=REPO))
    port = str(_free_port())
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), port, str(outdir)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=420)[0].decode() for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """The shared 2-process gloo world: launched once, every non-slow
    suite's predictions saved under the returned directory."""
    outdir = tmp_path_factory.mktemp("mpworld")
    _launch_pair(_WORKER, outdir)
    return outdir


def _serial_binary(rounds=5):
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(11)
    n = 700
    X = rng.randn(n, 6)
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 2] ** 2 * 0.2) > 0).astype(float)
    pred = lgb.train({"objective": "binary", "num_leaves": 7,
                      "min_data_in_leaf": 5, "verbosity": -1},
                     lgb.Dataset(X, y), rounds).predict(X)
    return pred


def test_two_process_training_matches_serial(world):
    p0 = np.load(world / "pred_0.npy")
    p1 = np.load(world / "pred_1.npy")
    np.testing.assert_allclose(p0, p1, atol=1e-7)  # ranks agree exactly
    w0 = np.load(world / "wpred_0.npy")
    w1 = np.load(world / "wpred_1.npy")
    np.testing.assert_allclose(w0, w1, atol=1e-7)
    assert np.isfinite(w0).all()
    v0 = np.load(world / "vpred_0.npy")
    v1 = np.load(world / "vpred_1.npy")
    np.testing.assert_allclose(v0, v1, atol=1e-7)  # ranks agree

    # serial baseline in THIS process (8-device mesh, single process)
    serial = _serial_binary()
    np.testing.assert_allclose(p0, serial, atol=2e-5)
    np.testing.assert_allclose(v0, serial, atol=2e-5)


def test_two_process_feature_learner_matches_serial(tmp_path):
    _launch_pair(_WORKER_FP, tmp_path)
    p0 = np.load(tmp_path / "fpred_0.npy")
    p1 = np.load(tmp_path / "fpred_1.npy")
    np.testing.assert_allclose(p0, p1, atol=1e-7)
    np.testing.assert_allclose(p0, _serial_binary(), atol=2e-5)


def test_two_process_pre_partition_dense_sparse_linear(world):
    """Disjoint per-process shards (pre_partition) + distributed bin
    finding reproduce full-data training (dataset_loader.cpp:1040's
    per-rank FindBin + allgather contract) — dense binary shards exactly,
    plus sparse shards (gathered nonzero samples + global zero fractions)
    and linear trees (row-sharded raw matrix) in the same world."""
    p0 = np.load(world / "ppred_0.npy")
    p1 = np.load(world / "ppred_1.npy")
    np.testing.assert_allclose(p0, p1, atol=1e-7)
    np.testing.assert_allclose(p0, _serial_binary(), atol=2e-4)

    # sparse + linear: ranks agree, quality sanity vs the targets
    # (mappers differ slightly from serial sampling, so exact-serial
    # parity is not asserted here)
    rng = np.random.RandomState(23)
    n = 800
    X = rng.randn(n, 6)
    y = (X[:, 0] * 2 - X[:, 1] + 0.3 * rng.randn(n))
    for tag in ("spred", "lpred"):
        p0 = np.load(world / f"{tag}_0.npy")
        p1 = np.load(world / f"{tag}_1.npy")
        np.testing.assert_allclose(p0, p1, atol=1e-6)  # ranks agree
        assert np.isfinite(p0).all()
        assert np.mean((p0 - y) ** 2) < np.var(y) * 0.6


# -- chaos: one worker of a collective dies mid-train ------------------------
_WORKER_CHAOS = textwrap.dedent("""
    import sys
    rank = int(sys.argv[1]); port = sys.argv[2]; outdir = sys.argv[3]
    resume = sys.argv[4] == "resume"
    sys.path.insert(0, {repo!r})
    import os
    import jax
    jax.config.update("jax_num_cpu_devices", 2)
    import lightgbm_tpu as lgb
    lgb.distributed.init(coordinator_address="127.0.0.1:" + port,
                         num_processes=2, process_id=rank)
    import numpy as np
    from lightgbm_tpu.utils.log import set_verbosity
    set_verbosity(-1)
    rng = np.random.RandomState(11)
    n = 700
    X = rng.randn(n, 6)
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 2] ** 2 * 0.2) > 0).astype(float)
    P = {{"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1, "tree_learner": "data",
          "checkpoint_dir": f"{{outdir}}/ck_{{rank}}"}}
    if resume:
        P["resume"] = "latest"
    bst = lgb.train(P, lgb.Dataset(X, y), 6)
    np.save(f"{{outdir}}/cpred_{{rank}}.npy", bst.predict(X))
""")


@pytest.mark.slow
@pytest.mark.chaos
def test_worker_killed_mid_collective_job_resumes(tmp_path):
    """PV-Tree-regime chaos (resilience/faults.py kill_at_iter+kill_rank):
    rank 1 of a 2-process data-parallel run is hard-killed entering
    iteration 3 — the host-side analogue of a preempted worker dying
    mid-allreduce.  The orchestrator (this test) reaps the survivor and
    relaunches the job with resume=latest; the resumed job completes
    from the checkpoint ring and reproduces serial training."""
    script = str(tmp_path / "worker_chaos.py")
    with open(script, "w") as fh:
        fh.write(_WORKER_CHAOS.format(repo=REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               LGBM_TPU_FAULTS="kill_at_iter=3,kill_rank=1")
    port = str(_free_port())
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), port, str(tmp_path), "fresh"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    out1 = procs[1].communicate(timeout=420)[0].decode()
    assert procs[1].returncode == 137, f"rank1 should die killed:\n{out1[-2000:]}"
    # the survivor is stuck in (or erroring out of) a collective whose
    # peer vanished; a real orchestrator reaps and reschedules the job
    procs[0].kill()
    procs[0].communicate(timeout=60)
    ck1 = tmp_path / "ck_1"
    assert ck1.is_dir() and any(f.startswith("ckpt_iter")
                                for f in os.listdir(ck1))

    env_resume = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    port = str(_free_port())
    procs = [subprocess.Popen(
        [sys.executable, script, str(r), port, str(tmp_path), "resume"],
        env=env_resume, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    outs = [p.communicate(timeout=420)[0].decode() for p in procs]
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"resumed worker failed:\n{out[-3000:]}"
    p0 = np.load(tmp_path / "cpred_0.npy")
    p1 = np.load(tmp_path / "cpred_1.npy")
    np.testing.assert_allclose(p0, p1, atol=1e-7)

    serial = _serial_binary(rounds=6)
    np.testing.assert_allclose(p0, serial, atol=2e-5)
