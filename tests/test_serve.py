"""Serving-layer tests (lightgbm_tpu.serve): bucketed predictor parity
with Booster.predict across bucket boundaries, micro-batcher correctness
under concurrent submitters, registry hot-swap atomicity, and end-to-end
HTTP smoke tests over localhost (slow-marked)."""

import json
import os
import sys
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serve import (MicroBatcher, ModelRegistry,
                                PredictionServer)

SMALL = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1}


@pytest.fixture(scope="module")
def booster(binary_data):
    X, y = binary_data
    p = {**SMALL, "objective": "binary"}
    return lgb.train(p, lgb.Dataset(X, y, params=p), 15)


@pytest.fixture(scope="module")
def predictor(booster):
    return booster.to_predictor(warmup=True)


# -- shape buckets ----------------------------------------------------------
def test_bucket_ladder():
    from lightgbm_tpu.models.tree import bucket_rows
    assert [bucket_rows(n) for n in (0, 1, 2, 8, 9, 64, 65, 512, 513,
                                     4096, 4097, 10000)] == \
        [1, 1, 8, 8, 64, 64, 512, 512, 4096, 4096, 8192, 12288]


@pytest.mark.parametrize("n", [1, 7, 8, 9, 511, 513])
def test_bucket_parity(n, booster, predictor):
    """Bucketed predictor output is bitwise identical to Booster.predict
    across bucket boundaries."""
    rng = np.random.RandomState(n)
    Xs = rng.randn(n, 6)
    assert np.array_equal(predictor.predict(Xs), booster.predict(Xs))
    assert np.array_equal(predictor.predict(Xs, raw_score=True),
                          booster.predict(Xs, raw_score=True))


def test_zero_recompiles_after_warmup(predictor):
    r0 = predictor.stats.snapshot()["recompiles"]
    assert r0 >= 0
    rng = np.random.RandomState(0)
    for n in (1, 2, 3, 5, 9, 63, 65, 511, 513, 4096):
        predictor.predict(rng.randn(n, predictor.num_features))
    assert predictor.stats.snapshot()["recompiles"] == r0


def test_predictor_nan_and_single_row(booster, predictor):
    rng = np.random.RandomState(1)
    Xs = rng.randn(5, 6)
    Xs[2, 1] = np.nan
    assert np.array_equal(predictor.predict(Xs), booster.predict(Xs))
    # 1-D row is accepted as one request row
    assert np.array_equal(predictor.predict(Xs[0]),
                          booster.predict(Xs[0].reshape(1, -1)))


def test_multiclass_predictor_parity(multiclass_data):
    X, y = multiclass_data
    p = {**SMALL, "objective": "multiclass", "num_class": 3}
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), 8)
    pred = bst.to_predictor(warmup=True)
    rng = np.random.RandomState(3)
    for n in (1, 9, 130):
        Xs = rng.randn(n, 6)
        out = pred.predict(Xs)
        assert out.shape == (n, 3)
        assert np.array_equal(out, bst.predict(Xs))


def test_categorical_predictor_parity():
    """Categorical models take the sequential walk kind — parity must
    hold there too."""
    rng = np.random.RandomState(5)
    n = 600
    Xc = rng.randn(n, 6)
    Xc[:, 3] = rng.randint(0, 12, n)
    # the label hangs mostly on the CATEGORY so the trees must split on it
    y = ((Xc[:, 3] % 3 == 0) * 2.0 + 0.3 * Xc[:, 0] +
         0.3 * rng.randn(n) > 1.0).astype(np.float64)
    p = {**SMALL, "objective": "binary"}
    ds = lgb.Dataset(Xc, y, categorical_feature=[3], params=p)
    bst = lgb.train(p, ds, 10)
    pred = bst.to_predictor()
    info = pred.info()
    # the inference compiler routes categorical ensembles too (the
    # bitset-membership contraction) — and when it decides the walk it
    # must say why, never silently
    assert info["compiler"] in ("dense", "walk")
    if info["compiler"] == "dense":
        assert info["dense"]["has_cat"]
    else:
        assert info["fallback_reason"]
    Xq = rng.randn(9, 6)
    Xq[:, 3] = rng.randint(0, 14, 9)  # incl. unseen category 12/13
    assert np.array_equal(pred.predict(Xq), bst.predict(Xq))
    # the forced-walk path stays available and bitwise-consistent with
    # the sequential kernels
    pw = bst.to_predictor(compiler="walk")
    assert pw.info()["compiler"] == "walk"
    assert pw.info()["fallback_reason"] == "forced_walk"
    assert "seq" in pw.info()["kinds"]
    assert np.allclose(pw.predict(Xq), pred.predict(Xq), rtol=1e-6,
                       atol=1e-7)


def test_linear_tree_predictor_parity(regression_data):
    X, y = regression_data
    p = {**SMALL, "objective": "regression", "linear_tree": True}
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), 8)
    pred = bst.to_predictor()
    info = pred.info()
    if info["compiler"] == "dense":
        assert info["dense"]["has_linear"]
    else:
        assert info["kinds"] == ["dense_lin"]
    rng = np.random.RandomState(6)
    Xq = rng.randn(9, 6)
    Xq[3, 0] = np.nan  # linear leaves fall back to plain output on NaN
    assert np.array_equal(pred.predict(Xq), bst.predict(Xq))


def test_rf_predictor_parity(binary_data):
    """RF models predict the MEAN of tree outputs; the predictor must
    apply the same averaging."""
    X, y = binary_data
    p = {**SMALL, "objective": "binary", "boosting": "rf",
         "bagging_freq": 1, "bagging_fraction": 0.8}
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), 6)
    pred = bst.to_predictor()
    rng = np.random.RandomState(8)
    Xq = rng.randn(9, 6)
    assert np.array_equal(pred.predict(Xq), bst.predict(Xq))


def test_stats_counters(booster):
    pred = booster.to_predictor()
    pred.predict(np.zeros((3, 6), np.float32))
    pred.predict(np.zeros((70, 6), np.float32))
    s = pred.stats.snapshot()
    assert s["batches"] == 2 and s["rows"] == 73
    assert s["bucket_histogram"] == {"8": 1, "512": 1}
    assert s["latency_ms"]["p50"] > 0


# -- micro-batcher ----------------------------------------------------------
def test_batcher_concurrent_submitters(booster, predictor):
    rng = np.random.RandomState(7)
    inputs = [rng.randn(1 + (i * 13) % 40, 6) for i in range(24)]
    refs = [booster.predict(Xs) for Xs in inputs]
    mb = MicroBatcher(lambda X, raw: predictor.predict(X, raw_score=raw),
                      max_wait_ms=5.0)
    try:
        outs = [None] * len(inputs)

        def worker(lo, hi):
            for i in range(lo, hi):
                outs[i] = mb.predict(inputs[i])

        threads = [threading.Thread(target=worker, args=(i * 3, i * 3 + 3))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for out, ref in zip(outs, refs):
            assert np.array_equal(out, ref)
    finally:
        mb.close()


def test_batcher_bad_request_does_not_poison_batch(predictor):
    mb = MicroBatcher(lambda X, raw: predictor.predict(X, raw_score=raw),
                      max_wait_ms=20.0)
    try:
        good = mb.submit(np.zeros((2, 6), np.float32))
        bad = mb.submit(np.zeros((2, 9), np.float32))  # wrong width
        assert good.result(timeout=30).shape == (2,)
        with pytest.raises(Exception):
            bad.result(timeout=30)
    finally:
        mb.close()


# -- registry ---------------------------------------------------------------
def test_registry_basics(booster):
    reg = ModelRegistry()
    with pytest.raises(KeyError):
        reg.get()
    reg.load("a", booster, warmup=False)
    assert reg.get() is reg.get("a")  # single model needs no name
    reg.load("b", booster, warmup=False)
    with pytest.raises(KeyError):
        reg.get()  # ambiguous now
    info = reg.info()
    assert set(info) == {"a", "b"} and info["a"]["version"] == 1
    assert reg.evict("a") and not reg.evict("a")
    assert reg.names() == ["b"]


def test_registry_hot_swap_atomic(binary_data):
    """Readers racing a rollout must see exactly one version's output,
    never a mix."""
    X, y = binary_data
    p = {**SMALL, "objective": "binary"}
    b1 = lgb.train(p, lgb.Dataset(X, y, params=p), 5)
    b2 = lgb.train(p, lgb.Dataset(X, y, params=p), 9)
    rng = np.random.RandomState(11)
    Xq = rng.randn(9, 6)
    ref1, ref2 = b1.predict(Xq), b2.predict(Xq)
    assert not np.array_equal(ref1, ref2)
    reg = ModelRegistry()
    reg.load("m", b1, warmup=False)
    bad = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            out = reg.get("m").predict(Xq)
            if not (np.array_equal(out, ref1) or np.array_equal(out, ref2)):
                bad.append(out)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in readers:
        t.start()
    for i in range(6):
        reg.load("m", b2 if i % 2 == 0 else b1, warmup=False)
    stop.set()
    for t in readers:
        t.join()
    assert not bad, "hot-swap produced mixed-version outputs"
    assert reg.info()["m"]["version"] == 7


def test_registry_hot_swap_dense_atomic(binary_data):
    """Hot-swapping a DENSE-compiled model must rebuild the whole
    compiled program atomically: readers racing the rollout see exactly
    one version's output (path matrices and leaf tables can never come
    from different versions), and stats carry over the swap."""
    X, y = binary_data
    p = {**SMALL, "objective": "binary"}
    b1 = lgb.train(p, lgb.Dataset(X, y, params=p), 5)
    b2 = lgb.train(p, lgb.Dataset(X, y, params=p), 9)
    rng = np.random.RandomState(12)
    Xq = rng.randn(9, 6)
    reg = ModelRegistry()
    reg.load("m", b1, warmup=False, compiler="dense")
    assert reg.get("m").info()["compiler"] == "dense"
    ref1 = reg.get("m").predict(Xq)
    reg.load("m", b2, warmup=False, compiler="dense")
    ref2 = reg.get("m").predict(Xq)
    assert not np.array_equal(ref1, ref2)
    reg.get("m").predict(Xq)
    batches_before = reg.stats()["m"]["batches"]
    bad = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            out = reg.get("m").predict(Xq)
            if not (np.array_equal(out, ref1) or np.array_equal(out, ref2)):
                bad.append(out)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in readers:
        t.start()
    for i in range(6):
        reg.load("m", b1 if i % 2 == 0 else b2, warmup=False,
                 compiler="dense")
    stop.set()
    for t in readers:
        t.join()
    assert not bad, "dense hot-swap produced mixed-version outputs"
    # stats survive the swaps (the counters track the NAME, and the new
    # executable was fully built before the one-assignment swap)
    assert reg.stats()["m"]["batches"] > batches_before
    assert reg.info()["m"]["version"] == 8
    assert reg.info()["m"]["compiler"] == "dense"


def test_registry_swap_keeps_stats(booster):
    reg = ModelRegistry()
    reg.load("m", booster, warmup=False)
    reg.get("m").predict(np.zeros((2, 6), np.float32))
    before = reg.stats()["m"]["batches"]
    reg.load("m", booster, warmup=False)  # hot-swap, stats carry over
    assert reg.stats()["m"]["batches"] == before


def test_registry_load_failure_mid_hot_swap_leaves_old_serving(
        tmp_path, binary_data, booster):
    """A corrupt source mid-hot-swap surfaces the typed error and
    leaves the OLD predictor serving untouched — same version, same
    stats, never a torn or evicted entry."""
    from lightgbm_tpu.models.model_text import ModelCorruptError
    X, _ = binary_data
    good = str(tmp_path / "good.txt")
    booster.save_model(good)
    corrupt = str(tmp_path / "corrupt.txt")
    with open(good) as fh:
        text = fh.read()
    with open(corrupt, "w") as fh:
        fh.write(text[: len(text) // 3])        # truncated mid-field
    reg = ModelRegistry()
    reg.load("m", good, warmup=False)
    ref = reg.get("m").predict(X[:5])
    reg.get("m").predict(X[:5])
    batches_before = reg.stats()["m"]["batches"]
    with pytest.raises(ModelCorruptError):
        reg.load("m", corrupt, warmup=False)
    # old version intact: same predictions, same version, same source,
    # stats still accumulating on the same series
    assert np.array_equal(reg.get("m").predict(X[:5]), ref)
    info = reg.info()["m"]
    assert info["version"] == 1 and info["source"] == good
    reg.get("m").predict(X[:5])
    # two predicts since the failed swap (the parity check + this one)
    # landed on the SAME stats series — nothing was torn or reset
    assert reg.stats()["m"]["batches"] == batches_before + 2
    # a failed FIRST load leaves no phantom entry behind
    reg2 = ModelRegistry()
    with pytest.raises(ModelCorruptError):
        reg2.load("x", corrupt, warmup=False)
    assert reg2.names() == [] and reg2.stats() == {}
    reg2.load("x", good, warmup=False)          # name still usable
    assert reg2.info()["x"]["version"] == 1


def test_shutdown_drain_exactly_one_terminal_response(tmp_path,
                                                      binary_data,
                                                      booster):
    """Satellite: a queued request racing PredictionServer.shutdown()
    gets exactly one terminal response — a result, or a typed 5xx from
    the ServerClosed/draining path — never a hung future."""
    import http.client
    X, _ = binary_data
    reg = _slow_registry(tmp_path, booster, delay=0.15)
    srv = PredictionServer(reg, port=0, max_wait_ms=0.5,
                           max_batch_rows=1).start()
    row = X[0].tolist()
    results = []
    lock = threading.Lock()

    def hit():
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        try:
            out = _post(conn, "/predict", {"rows": [row]})
        except Exception as exc:      # severed mid-drain: terminal too
            out = ("conn_error", type(exc).__name__)
        with lock:
            results.append(out)
        conn.close()

    threads = [threading.Thread(target=hit) for _ in range(8)]
    for t in threads:
        t.start()
    import time
    time.sleep(0.2)            # some requests queued, one on device
    srv.shutdown()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads), \
        "a request hung through shutdown"
    assert len(results) == 8   # every request got a terminal outcome
    statuses = [r[0] for r in results]
    assert all(s in (200, 503, 504, "conn_error") for s in statuses), \
        statuses
    assert statuses.count(200) >= 1  # in-flight work completed


# -- end-to-end HTTP --------------------------------------------------------
def _post(conn, path, payload):
    conn.request("POST", path, json.dumps(payload),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, json.loads(resp.read())


def _get(conn, path):
    conn.request("GET", path)
    resp = conn.getresponse()
    return resp.status, json.loads(resp.read())


@pytest.mark.slow
def test_serve_e2e_http(tmp_path, binary_data, booster):
    """The acceptance flow: a warm server answers 1000 sequential
    single-row /predict requests with ZERO recompiles after warmup,
    verified through the /stats counter; plus /healthz, /models listing,
    and an over-HTTP hot-swap."""
    import http.client
    X, y = binary_data
    model_file = str(tmp_path / "model.txt")
    booster.save_model(model_file)
    reg = ModelRegistry()
    reg.load("model", model_file, warmup=True)
    srv = PredictionServer(reg, port=0, max_wait_ms=0.5).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        status, health = _get(conn, "/healthz")
        assert status == 200 and health["models"] == ["model"]
        status, models = _get(conn, "/models")
        assert status == 200 and models["model"]["num_trees"] == 15
        recompiles0 = _get(conn, "/stats")[1]["model"]["recompiles"]

        row = X[0].tolist()
        ref = float(booster.predict(X[:1])[0])
        for _ in range(1000):
            status, body = _post(conn, "/predict", {"rows": [row]})
            assert status == 200
            assert body["predictions"][0] == pytest.approx(ref, abs=0.0)
        status, stats = _get(conn, "/stats")
        assert stats["model"]["recompiles"] == recompiles0, \
            "single-row traffic recompiled after warmup"
        assert stats["model"]["requests"] >= 1000
        assert stats["model"]["bucket_histogram"].get("1", 0) >= 1000

        # error paths
        assert _post(conn, "/predict", {})[0] == 400
        assert _post(conn, "/predict", {"rows": [row],
                                        "model": "nope"})[0] == 404
        assert _get(conn, "/bogus")[0] == 404

        # hot-swap over HTTP: predictions switch to the new version
        p = {**SMALL, "objective": "binary"}
        b2 = lgb.train(p, lgb.Dataset(X, y, params=p), 7)
        model2 = str(tmp_path / "model2.txt")
        b2.save_model(model2)
        status, info = _post(conn, "/models", {"name": "model",
                                               "file": model2})
        assert status == 200 and info["num_trees"] == 7
        _, body = _post(conn, "/predict", {"row": row})
        assert body["predictions"][0] == pytest.approx(
            float(b2.predict(X[:1])[0]), abs=0.0)
    finally:
        srv.shutdown()


@pytest.mark.slow
def test_serve_cli_subprocess(tmp_path, booster, binary_data):
    """`python -m lightgbm_tpu serve model.txt` boots, answers /predict,
    and dies cleanly."""
    import http.client
    import re
    import subprocess
    import time
    X, _ = binary_data
    model_file = str(tmp_path / "model.txt")
    booster.save_model(model_file)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo,
           "PYTHONUNBUFFERED": "1"}
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "serve", model_file,
         "port=0", "warmup=0"],
        cwd=repo, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        port = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line and proc.poll() is not None:
                break
            m = re.search(r"listening on http://[^:]+:(\d+)", line)
            if m:
                port = int(m.group(1))
                break
        assert port, "server never reported its port"
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        status, body = _post(conn, "/predict", {"row": X[0].tolist()})
        assert status == 200
        assert body["predictions"][0] == pytest.approx(
            float(booster.predict(X[:1])[0]), abs=1e-12)
    finally:
        proc.terminate()
        proc.wait(timeout=30)


# -- admission control / degradation (resilience subsystem) -----------------
def _post_full(conn, path, payload):
    """Like _post but also returns the response headers."""
    conn.request("POST", path, json.dumps(payload),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, json.loads(resp.read()), dict(resp.getheaders())


def _slow_registry(tmp_path, booster, delay):
    import time
    model_file = str(tmp_path / "model.txt")
    booster.save_model(model_file)
    reg = ModelRegistry()
    reg.load("model", model_file, warmup=True)
    pred = reg.get("model")
    orig = pred.predict

    def slow_predict(X, raw_score=False, request_ids=()):
        # keep the real predict's signature: the batcher propagates
        # request_ids into predictors that accept them (PR 14), and a
        # patched predict without the kwarg turns every batch into a
        # TypeError 400
        time.sleep(delay)
        return orig(X, raw_score=raw_score, request_ids=request_ids)
    pred.predict = slow_predict
    return reg


@pytest.mark.slow
@pytest.mark.chaos
def test_serve_load_shed_503_and_degraded_healthz(tmp_path, binary_data,
                                                  booster):
    """Synthetic overload: a slow model + a 4-row queue bound. Admitted
    requests succeed, over-limit requests are shed with 503 +
    Retry-After, and /healthz flips to degraded while shedding."""
    import http.client
    X, _ = binary_data
    reg = _slow_registry(tmp_path, booster, delay=0.4)
    srv = PredictionServer(reg, port=0, max_wait_ms=0.5, max_batch_rows=4,
                           max_queue_rows=4).start()
    try:
        row = X[0].tolist()
        results = []
        lock = threading.Lock()

        def hit():
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=60)
            out = _post_full(conn, "/predict", {"rows": [row]})
            with lock:
                results.append(out)
            conn.close()

        threads = [threading.Thread(target=hit) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        statuses = [r[0] for r in results]
        assert statuses.count(200) >= 1, statuses
        assert statuses.count(503) >= 1, statuses
        shed = next(r for r in results if r[0] == 503)
        assert "queue is full" in shed[1]["error"]
        assert int(shed[2]["Retry-After"]) >= 1
        # degraded while sheds are recent — still HTTP 200
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        status, health = _get(conn, "/healthz")
        assert status == 200
        assert health["status"] == "degraded"
        assert any("shedding" in r for r in health["reasons"])
    finally:
        srv.shutdown()


@pytest.mark.slow
@pytest.mark.chaos
def test_serve_deadline_504(tmp_path, binary_data, booster):
    """A request whose deadline elapses while the device is busy gets
    504 instead of hanging its handler thread; an unhurried request on
    the same server still succeeds."""
    import http.client
    X, _ = binary_data
    reg = _slow_registry(tmp_path, booster, delay=0.5)
    srv = PredictionServer(reg, port=0, max_wait_ms=0.5,
                           max_batch_rows=1).start()
    try:
        row = X[0].tolist()
        occupier = threading.Thread(target=lambda: _post(
            http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60),
            "/predict", {"rows": [row]}))
        occupier.start()
        import time
        time.sleep(0.15)  # the occupier's batch is now on the device
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        status, body, _ = _post_full(conn, "/predict",
                                     {"rows": [row], "deadline_ms": 100})
        assert status == 504, body
        occupier.join(60)
        status, body = _post(conn, "/predict", {"rows": [row]})
        assert status == 200
    finally:
        srv.shutdown()


@pytest.mark.slow
@pytest.mark.chaos
def test_serve_sigterm_drains_and_exits_128_plus_signum(tmp_path,
                                                        booster,
                                                        binary_data):
    """Satellite: the serve CLI handles SIGTERM like training's
    PreemptionGuard — stop accepting, drain, exit 128+15 — and
    announces its port through port_file (the fleet supervisor's
    discovery channel)."""
    import http.client
    import signal as _signal
    import subprocess
    import time
    X, _ = binary_data
    model_file = str(tmp_path / "model.txt")
    booster.save_model(model_file)
    port_file = str(tmp_path / "serve.port")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo}
    proc = subprocess.Popen(
        [sys.executable, "-m", "lightgbm_tpu", "serve", model_file,
         "port=0", "warmup=0", f"port_file={port_file}"],
        cwd=repo, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 120
        port = None
        while time.monotonic() < deadline and port is None:
            try:
                with open(port_file) as fh:
                    port = int(fh.read().strip())
            except (OSError, ValueError):
                time.sleep(0.05)
        assert port, "port_file never appeared"
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        status, body = _post(conn, "/predict", {"row": X[0].tolist()})
        assert status == 200
        conn.close()
        proc.send_signal(_signal.SIGTERM)
        rc = proc.wait(timeout=60)
        assert rc == 128 + 15, f"exit code {rc}"
        # the socket is gone: a late request is refused, not hung
        with pytest.raises(OSError):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=5)
            conn.request("GET", "/healthz")
            conn.getresponse()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(30)
