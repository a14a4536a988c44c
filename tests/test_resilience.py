"""Fault-tolerance suite (lightgbm_tpu.resilience, ISSUE 6): proves —
with injected faults, not assumptions — that

  * checkpoints are atomic on disk and resume is BIT-identical to an
    uninterrupted run (serial, quantized, and the DP-wave/reduce-scatter
    path) including bagging/feature-fraction RNG streams, eval history
    and early-stopping bookkeeping;
  * a SIGTERM mid-train drains the in-flight iteration and flushes one
    final checkpoint (in-process and real-subprocess);
  * a hard kill (``os._exit``, the chaos layer's ``kill_at_iter``)
    leaves a loadable snapshot ring behind;
  * restores against the wrong dataset / seeds fail loudly;
  * truncated model files raise typed :class:`ModelCorruptError`;
  * the micro-batcher sheds over-limit load, expires deadlines and
    fails queued work on close instead of hanging callers.
"""

import os
import signal
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import (Checkpoint, CheckpointError, ModelCorruptError,
                          TrainingPreempted, load_checkpoint)
from lightgbm_tpu.io_utils import atomic_write_bytes, atomic_write_text
from lightgbm_tpu.resilience.admission import (DeadlineExceeded,
                                               QueueFullError, ServerClosed)
from lightgbm_tpu.resilience.faults import InjectedFault, faults
from lightgbm_tpu.serve import MicroBatcher

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# bagging + feature sampling ON so a resume that mis-restores the RNG
# position cannot stay bit-identical by accident
PARAMS = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1, "seed": 7, "bagging_fraction": 0.7,
          "bagging_freq": 1, "feature_fraction": 0.8}
ROUNDS = 8
CRASH_AT = 4


@pytest.fixture(autouse=True)
def _clear_faults():
    faults.clear()
    yield
    faults.clear()


def _data(seed=0, n=400, f=6):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    return X, y


def _crash_resume_roundtrip(tmp_path, extra_params, tag):
    """Train uninterrupted; train again with a crash injected at
    iteration CRASH_AT; resume; assert model text + predictions are
    bit-identical."""
    X, y = _data()
    P = {**PARAMS, **extra_params}
    full = lgb.train({**P, "checkpoint_dir": str(tmp_path / f"{tag}_full")},
                     lgb.Dataset(X, y), ROUNDS)
    ck = str(tmp_path / f"{tag}_ck")
    faults.configure(f"crash_at_iter={CRASH_AT}")
    with pytest.raises(InjectedFault):
        lgb.train({**P, "checkpoint_dir": ck}, lgb.Dataset(X, y), ROUNDS)
    faults.clear()
    resumed = lgb.train({**P, "checkpoint_dir": ck, "resume": "latest"},
                        lgb.Dataset(X, y), ROUNDS)
    # model_to_string excludes checkpoint_dir/resume from the params dump,
    # so the comparison is byte-for-byte with no normalization
    assert resumed.model_to_string() == full.model_to_string()
    np.testing.assert_array_equal(resumed.predict(X), full.predict(X))
    return full, resumed


# -- atomic writes -----------------------------------------------------------
def test_atomic_write_survives_writer_crash(tmp_path):
    path = str(tmp_path / "f.txt")
    atomic_write_text(path, "old content")

    def exploding_writer(fh):
        fh.write(b"half a new fi")
        raise RuntimeError("crash mid-write")

    with pytest.raises(RuntimeError):
        atomic_write_bytes(path, writer=exploding_writer)
    with open(path) as fh:
        assert fh.read() == "old content"
    assert os.listdir(tmp_path) == ["f.txt"]  # temp cleaned up


def test_atomic_write_replaces(tmp_path):
    path = str(tmp_path / "f.txt")
    atomic_write_text(path, "v1")
    atomic_write_text(path, "v2")
    with open(path) as fh:
        assert fh.read() == "v2"


# -- checkpoint bundle -------------------------------------------------------
def test_checkpoint_bundle_roundtrip():
    ck = Checkpoint(
        iteration=5, model_text="tree\nversion=v3\n",
        score=np.arange(6, dtype=np.float32),
        valid_names=["valid_0"],
        valid_scores=[np.ones(3, np.float32) * 0.25],
        eval_history={"valid_0": {"auc": [0.5, 0.6]}},
        early_stop=[{"rounds": 3, "first_metric_name": "auc",
                     "trackers": None}],
        rng_state={"seed": 7, "bagging_seed": 3},
        fingerprint={"num_data": 6, "data_crc32": 123},
        params={"objective": "binary"},
        prev_iter_leaves=[7])
    back = Checkpoint.from_bytes(ck.to_bytes())
    assert back.iteration == 5
    assert back.model_text == ck.model_text
    np.testing.assert_array_equal(back.score, ck.score)
    assert back.valid_names == ["valid_0"]
    np.testing.assert_array_equal(back.valid_scores[0], ck.valid_scores[0])
    assert back.eval_history == ck.eval_history
    assert back.early_stop == ck.early_stop
    assert back.rng_state == {"seed": 7, "bagging_seed": 3}
    assert back.fingerprint["data_crc32"] == 123
    assert back.prev_iter_leaves == [7]


def test_truncated_checkpoint_bundle_rejected():
    data = Checkpoint(iteration=1, model_text="tree\n",
                      score=np.zeros(4, np.float32)).to_bytes()
    with pytest.raises(CheckpointError, match="not a readable checkpoint"):
        Checkpoint.from_bytes(data[:len(data) // 2], source="half.npz")
    with pytest.raises(CheckpointError, match="garbage.npz"):
        Checkpoint.from_bytes(b"\x00garbage" * 10, source="garbage.npz")


def test_checkpoint_ring_bounded_and_latest(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "ring")
    lgb.train({**PARAMS, "checkpoint_dir": ck, "snapshot_freq": 1,
               "checkpoint_keep": 2,
               # snapshot_freq also writes model-text snapshots; keep
               # them out of the process CWD
               "output_model": str(tmp_path / "model.txt")},
              lgb.Dataset(X, y), 6)
    names = sorted(os.listdir(ck))
    assert names == ["LATEST", "ckpt_iter00000005.npz",
                     "ckpt_iter00000006.npz"]
    assert load_checkpoint(ck).iteration == 6


def test_latest_pointer_falls_back_to_newest_ring_entry(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "ring")
    lgb.train({**PARAMS, "checkpoint_dir": ck}, lgb.Dataset(X, y), 3)
    os.unlink(os.path.join(ck, "LATEST"))  # crash between write + repoint
    assert load_checkpoint(ck).iteration == 3


# -- crash / resume bit-identity ---------------------------------------------
def test_crash_resume_bit_identity_serial(tmp_path):
    _crash_resume_roundtrip(tmp_path, {}, "serial")


def test_crash_resume_bit_identity_quantized(tmp_path):
    _crash_resume_roundtrip(
        tmp_path, {"use_quantized_grad": True, "stochastic_rounding": True},
        "quant")


@pytest.mark.slow  # 8-device mesh compile; the CI chaos step runs it
def test_crash_resume_bit_identity_dp_wave(tmp_path):
    # the DP-wave reduce-scatter path on the virtual 8-device mesh
    # (PR 4's parity target); quantized so DP == serial is bit-exact
    _crash_resume_roundtrip(
        tmp_path,
        {"tree_learner": "data", "tree_grow_mode": "wave",
         "use_quantized_grad": True, "stochastic_rounding": False,
         "num_devices": 8},
        "dpwave")


def test_crash_resume_multiclass(tmp_path):
    X, _ = _data(n=300)
    rng = np.random.RandomState(3)
    y = rng.randint(0, 3, 300).astype(np.float64)
    P = {"objective": "multiclass", "num_class": 3, "num_leaves": 5,
         "verbosity": -1, "seed": 11}
    full = lgb.train(P, lgb.Dataset(X, y), 6)
    ck = str(tmp_path / "mc")
    faults.configure("crash_at_iter=3")
    with pytest.raises(InjectedFault):
        lgb.train({**P, "checkpoint_dir": ck}, lgb.Dataset(X, y), 6)
    faults.clear()
    resumed = lgb.train({**P, "checkpoint_dir": ck, "resume": "latest"},
                        lgb.Dataset(X, y), 6)
    # model_to_string excludes checkpoint_dir/resume from the params dump,
    # so the comparison is byte-for-byte with no normalization
    assert resumed.model_to_string() == full.model_to_string()
    np.testing.assert_array_equal(resumed.predict(X), full.predict(X))


def test_resume_restores_eval_history_and_early_stop(tmp_path):
    X, y = _data()
    Xv, yv = _data(seed=9, n=150)
    # share PARAMS' (num_leaves, N, F) shape so the grower compile is
    # reused across the file instead of paying a fresh jit here; also
    # exercises early-stop resume together with bagging state
    P = {**PARAMS, "early_stopping_round": 3, "metric": "binary_logloss"}

    def run(params, rounds, resume=False):
        ds = lgb.Dataset(X, y)
        dv = ds.create_valid(Xv, yv)
        hist = {}
        bst = lgb.train({**params, **({"resume": "latest"} if resume
                                      else {})}, ds, rounds,
                        valid_sets=[dv],
                        callbacks=[lgb.record_evaluation(hist)])
        return bst, hist

    full, hist_full = run(P, 30)
    ck = str(tmp_path / "es")
    run({**P, "checkpoint_dir": ck}, 5)
    resumed, hist_res = run({**P, "checkpoint_dir": ck}, 30, resume=True)
    assert resumed.best_iteration == full.best_iteration
    assert resumed.num_trees() == full.num_trees()
    assert hist_res == hist_full  # refilled across the preemption


def test_resume_latest_cold_start_trains_fresh(tmp_path):
    X, y = _data()
    bst = lgb.train({**PARAMS, "checkpoint_dir": str(tmp_path / "empty"),
                     "resume": "latest"}, lgb.Dataset(X, y), 5)
    assert bst.num_trees() == 5


# -- restore validation ------------------------------------------------------
def test_fingerprint_mismatch_rejected(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "fp")
    lgb.train({**PARAMS, "checkpoint_dir": ck}, lgb.Dataset(X, y), 3)
    X2, y2 = _data(seed=5)  # different rows, same shape
    with pytest.raises(CheckpointError, match="does not match"):
        lgb.train({**PARAMS, "checkpoint_dir": ck, "resume": "latest"},
                  lgb.Dataset(X2, y2), 6)


def test_seed_change_rejected(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "seed")
    lgb.train({**PARAMS, "checkpoint_dir": ck}, lgb.Dataset(X, y), 3)
    with pytest.raises(CheckpointError, match="RNG seed"):
        lgb.train({**PARAMS, "seed": 8, "checkpoint_dir": ck,
                   "resume": "latest"}, lgb.Dataset(X, y), 6)


def test_stopping_rounds_change_rejected(tmp_path):
    X, y = _data()
    Xv, yv = _data(seed=9, n=150)
    P = {**PARAMS, "metric": "binary_logloss"}

    def run(rounds_patience, resume=False, first_metric_only=False):
        ds = lgb.Dataset(X, y)
        lgb.train({**P, "checkpoint_dir": str(tmp_path / "esr"),
                   **({"resume": "latest"} if resume else {})},
                  ds, 6, valid_sets=[ds.create_valid(Xv, yv)],
                  callbacks=[lgb.early_stopping(
                      rounds_patience, first_metric_only=first_metric_only,
                      verbose=False)])

    run(10)
    with pytest.raises(CheckpointError, match="stopping_rounds"):
        run(5, resume=True)
    with pytest.raises(CheckpointError, match="first_metric_only"):
        run(10, resume=True, first_metric_only=True)


def test_atomic_write_concurrent_same_target(tmp_path):
    """Concurrent writers to one path must each publish a complete payload
    — never an interleaved hybrid — which requires per-call temp names."""
    target = str(tmp_path / "model.txt")
    payloads = [bytes([i]) * 4096 for i in range(8)]
    errs = []

    def write(p):
        try:
            for _ in range(20):
                atomic_write_bytes(target, p)
        except BaseException as e:  # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=write, args=(p,)) for p in payloads]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    with open(target, "rb") as fh:
        data = fh.read()
    assert data in payloads  # one winner, intact
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


def test_objective_change_rejected(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "obj")
    lgb.train({**PARAMS, "checkpoint_dir": ck}, lgb.Dataset(X, y), 3)
    with pytest.raises(CheckpointError, match="objective"):
        lgb.train({**PARAMS, "objective": "regression",
                   "checkpoint_dir": ck, "resume": "latest"},
                  lgb.Dataset(X, y), 6)


def test_dart_checkpoint_rejected(tmp_path):
    X, y = _data()
    ck = str(tmp_path / "dart")
    lgb.train({**PARAMS, "boosting": "dart", "checkpoint_dir": ck},
              lgb.Dataset(X, y), 3)
    with pytest.raises(ValueError, match="dart"):
        lgb.train({**PARAMS, "boosting": "dart", "checkpoint_dir": ck,
                   "resume": "latest"}, lgb.Dataset(X, y), 6)


# -- preemption (SIGTERM) ----------------------------------------------------
def test_sigterm_in_process_flushes_and_resumes(tmp_path):
    """A SIGTERM arriving mid-train (sent from a watchdog thread, the
    closest in-process analogue of a TPU preemption notice) drains the
    iteration, flushes a final checkpoint, raises TrainingPreempted —
    and the resumed run is bit-identical to one that never stopped."""
    X, y = _data()
    full = lgb.train(PARAMS, lgb.Dataset(X, y), ROUNDS)
    ck = str(tmp_path / "sig")
    fired = threading.Event()

    def kill_at(env):
        if env.iteration == CRASH_AT and not fired.is_set():
            fired.set()
            os.kill(os.getpid(), signal.SIGTERM)
    kill_at.before_iteration = True

    with pytest.raises(TrainingPreempted) as exc_info:
        lgb.train({**PARAMS, "checkpoint_dir": ck}, lgb.Dataset(X, y),
                  ROUNDS, callbacks=[kill_at])
    exc = exc_info.value
    assert exc.signum == signal.SIGTERM
    assert exc.checkpoint and os.path.exists(exc.checkpoint)
    # the in-flight iteration was drained, not abandoned
    assert load_checkpoint(ck).iteration == CRASH_AT + 1
    resumed = lgb.train({**PARAMS, "checkpoint_dir": ck, "resume": "latest"},
                        lgb.Dataset(X, y), ROUNDS)
    # model_to_string excludes checkpoint_dir/resume from the params dump,
    # so the comparison is byte-for-byte with no normalization
    assert resumed.model_to_string() == full.model_to_string()


_CHILD_COMMON = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import lightgbm_tpu as lgb
    lgb.set_verbosity(-1)
    rng = np.random.RandomState(0)
    X = rng.randn(400, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(400) > 0).astype(float)
    P = {{"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "verbosity": -1, "seed": 7, "bagging_fraction": 0.7,
          "bagging_freq": 1, "feature_fraction": 0.8,
          "checkpoint_dir": sys.argv[1]}}
""")


@pytest.mark.slow  # subprocess + jax import; the CI chaos step runs it
def test_sigterm_subprocess_flushes_checkpoint(tmp_path):
    """Real preemption shape: SIGTERM a separate training process, it
    exits AFTER flushing a loadable final checkpoint."""
    ck = str(tmp_path / "ck")
    script = _CHILD_COMMON.format(repo=REPO) + textwrap.dedent("""
        import time
        from lightgbm_tpu import TrainingPreempted
        def slow(env):
            if env.iteration == 1:
                print("TRAINING", flush=True)
            time.sleep(0.05)
        slow.before_iteration = True
        try:
            lgb.train(P, lgb.Dataset(X, y), 500, callbacks=[slow])
        except TrainingPreempted as exc:
            print("FLUSHED", exc.checkpoint, flush=True)
            sys.exit(43)
        sys.exit(0)
    """)
    proc = subprocess.Popen([sys.executable, "-c", script, ck],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        # wait until the loop is demonstrably mid-train, then preempt
        line = ""
        for line in proc.stdout:
            if "TRAINING" in line:
                break
        assert "TRAINING" in line, "child never started training"
        proc.send_signal(signal.SIGTERM)
        out = proc.stdout.read()
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 43, f"child exited {rc}: {out}"
    assert "FLUSHED" in out
    ckpt = load_checkpoint(ck)
    assert 0 < ckpt.iteration < 500


@pytest.mark.slow  # subprocess + jax import; the CI chaos step runs it
def test_kill_at_iter_subprocess_leaves_resumable_ring(tmp_path):
    """The chaos layer's hard kill (os._exit mid-train, no flush, no
    atexit — a preempted/OOM-killed worker): the atomic ring written so
    far must be loadable and the resumed run bit-identical."""
    ck = str(tmp_path / "ck")
    script = _CHILD_COMMON.format(repo=REPO) + textwrap.dedent("""
        lgb.train(P, lgb.Dataset(X, y), 10)
        sys.exit(0)  # unreachable: the armed fault kills at iteration 6
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script, ck], capture_output=True, text=True,
        timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "LGBM_TPU_FAULTS": "kill_at_iter=6"})
    assert proc.returncode == 137, proc.stdout + proc.stderr
    ckpt = load_checkpoint(ck)
    assert ckpt.iteration == 6  # snapshots through the kill boundary

    # resume in THIS process against identically-built data
    rng = np.random.RandomState(0)
    X = rng.randn(400, 6)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(400) > 0).astype(float)
    full = lgb.train(PARAMS, lgb.Dataset(X, y), 10)
    resumed = lgb.train({**PARAMS, "checkpoint_dir": ck, "resume": "latest"},
                        lgb.Dataset(X, y), 10)
    # model_to_string excludes checkpoint_dir/resume from the params dump,
    # so the comparison is byte-for-byte with no normalization
    assert resumed.model_to_string() == full.model_to_string()


# -- device-loss fault -------------------------------------------------------
def test_device_loss_is_an_error_not_a_cpu_run(monkeypatch):
    """An accelerator that cannot be initialised raises out of the
    backend probe; nothing re-pins the process to the CPU."""
    import jax
    from lightgbm_tpu.utils import backend
    monkeypatch.setattr(backend, "_resolved", None)
    before = jax.config.jax_platforms
    faults.configure("device_loss=1")
    try:
        with pytest.raises(RuntimeError, match="device lost"):
            backend.default_backend()
    finally:
        faults.clear()
    assert backend._resolved is None
    assert jax.config.jax_platforms == before


def test_cpu_nobody_asked_for_is_an_error(monkeypatch):
    """JAX settling on the CPU by itself (no JAX_PLATFORMS=cpu) must not
    pass for a run on the chip."""
    import types
    from lightgbm_tpu.utils import backend
    monkeypatch.setattr(backend, "_resolved", None)
    monkeypatch.setattr(backend, "jax", types.SimpleNamespace(
        default_backend=lambda: "cpu",
        config=types.SimpleNamespace(jax_platforms=None)))
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        backend.default_backend()


def test_fault_plan_env_parse():
    from lightgbm_tpu.resilience.faults import _parse_spec
    assert _parse_spec("crash_at_iter=3, kill_rank=1") == \
        {"crash_at_iter": 3, "kill_rank": 1}
    with pytest.raises(ValueError):
        _parse_spec("bogus")


# -- corrupt model files -----------------------------------------------------
def test_truncated_model_file_raises_typed_error(tmp_path):
    X, y = _data()
    bst = lgb.train(PARAMS, lgb.Dataset(X, y), 5)
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    full = open(path, "rb").read()
    trunc = str(tmp_path / "trunc.txt")
    with open(trunc, "wb") as fh:
        fh.write(full[:len(full) // 2])  # crash-truncated snapshot
    with pytest.raises(ModelCorruptError) as exc_info:
        lgb.Booster(model_file=trunc)
    assert "trunc.txt" in str(exc_info.value)
    assert exc_info.value.offset >= 0
    # the intact file still loads
    assert lgb.Booster(model_file=path).num_trees() == 5


def test_garbage_model_file_raises_typed_error(tmp_path):
    bad = str(tmp_path / "garbage.txt")
    with open(bad, "w") as fh:
        fh.write("this is not a model\nkey=value\n")
    with pytest.raises(ModelCorruptError, match="tree"):
        lgb.Booster(model_file=bad)
    raw = str(tmp_path / "raw.bin")
    with open(raw, "wb") as fh:
        fh.write(bytes(range(256)) * 8)
    with pytest.raises(ModelCorruptError, match="utf-8"):
        lgb.Booster(model_file=raw)


def test_short_field_in_model_rejected(tmp_path):
    X, y = _data()
    bst = lgb.train(PARAMS, lgb.Dataset(X, y), 3)
    lines = bst.model_to_string().splitlines()
    # chop values off a leaf_value line: mid-line truncation that keeps
    # the overall block structure intact must still be caught
    for i, ln in enumerate(lines):
        if ln.startswith("leaf_value=") and len(ln.split()) > 2:
            lines[i] = " ".join(ln.split()[:-1])
            break
    with pytest.raises(ModelCorruptError, match="leaf_value"):
        lgb.Booster(model_str="\n".join(lines))


# -- batcher admission control ----------------------------------------------
def _slow_predict(delay):
    def fn(X, raw):
        time.sleep(delay)
        return np.zeros(X.shape[0], np.float32)
    return fn


def test_batcher_close_fails_queued_requests_promptly():
    mb = MicroBatcher(_slow_predict(1.0), max_batch_rows=1, name="t_close")
    first = mb.submit(np.zeros((1, 3)))
    time.sleep(0.1)  # worker now busy with `first`
    queued = mb.submit(np.zeros((1, 3)))
    t0 = time.monotonic()
    mb.close(timeout=0.1)
    assert time.monotonic() - t0 < 0.8  # no waiting out the device call
    with pytest.raises(ServerClosed):
        queued.result(timeout=1.0)
    with pytest.raises(ServerClosed):
        mb.submit(np.zeros((1, 3)))
    first.result(timeout=5.0)  # in-flight work still completes


def test_batcher_queue_full_sheds():
    mb = MicroBatcher(_slow_predict(0.4), max_batch_rows=4,
                      max_queue_rows=8, name="t_shed")
    try:
        futs = [mb.submit(np.zeros((1, 3)))]
        time.sleep(0.1)  # worker picked up the first request
        futs += [mb.submit(np.zeros((4, 3))), mb.submit(np.zeros((4, 3)))]
        with pytest.raises(QueueFullError) as exc_info:
            mb.submit(np.zeros((1, 3)))
        assert exc_info.value.retry_after > 0
        assert exc_info.value.limit_rows == 8
        for f in futs:  # shed protected the admitted work
            assert f.result(timeout=10.0) is not None
    finally:
        mb.close()


def test_batcher_deadline_expires_queued_work():
    mb = MicroBatcher(_slow_predict(0.5), max_batch_rows=1, name="t_dl")
    try:
        mb.submit(np.zeros((1, 3)))  # occupies the worker
        time.sleep(0.05)
        with pytest.raises(DeadlineExceeded):
            mb.predict(np.zeros((1, 3)), timeout_s=0.1)
    finally:
        mb.close()


def test_batcher_worker_survives_error_on_expired_future():
    """A predict_fn failure racing a client-side deadline expiry must not
    kill the worker thread: the error-path set_exception hits an
    already-failed future and has to swallow InvalidStateError."""
    def fail_slowly(X, raw):
        time.sleep(0.4)
        raise RuntimeError("device fell over")

    mb = MicroBatcher(fail_slowly, max_batch_rows=1, name="t_err_race")
    try:
        with pytest.raises(DeadlineExceeded):
            # expires while the worker is inside fail_slowly; the worker's
            # subsequent set_exception lands on a done future
            mb.predict(np.zeros((1, 3)), timeout_s=0.1)
        time.sleep(0.5)  # let the worker hit the race
        # a dead worker would leave this queued forever; a live one fails
        # it promptly with the predict_fn's error
        with pytest.raises(RuntimeError, match="device fell over"):
            mb.predict(np.zeros((1, 3)), timeout_s=5.0)
    finally:
        mb.close()


def test_batcher_no_deadline_unaffected():
    mb = MicroBatcher(_slow_predict(0.0), name="t_ok")
    try:
        out = mb.predict(np.ones((3, 2)), timeout_s=5.0)
        assert out.shape == (3,)
    finally:
        mb.close()


# -- telemetry export --------------------------------------------------------
def test_resilience_metrics_registered(tmp_path):
    from lightgbm_tpu.telemetry.metrics import default_registry
    X, y = _data()
    ck = str(tmp_path / "tele")
    faults.configure("crash_at_iter=2")
    with pytest.raises(InjectedFault):
        lgb.train({**PARAMS, "checkpoint_dir": ck}, lgb.Dataset(X, y), 5)
    faults.clear()
    lgb.train({**PARAMS, "checkpoint_dir": ck, "resume": "latest"},
              lgb.Dataset(X, y), 5)
    snap = default_registry().snapshot()
    assert "checkpoint_write_seconds" in snap
    assert any(s["value"] >= 1 for s in snap["resume_total"]["series"])
    assert any(s["labels"].get("fault") == "crash_at_iter"
               for s in snap["faults_injected_total"]["series"])
