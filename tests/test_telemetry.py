"""Telemetry subsystem tests (lightgbm_tpu/telemetry/).

Covers: the metrics registry primitives and their thread-safety, the
shared percentile/sliding-window implementation serve/stats now rides
on, span tracing + chrome export (spans always annotate; only the event
list waits on the tracer), TrainRecord accumulation through real training
(set-up seconds, pass kinds), the bit-identical-training contract, the trace-time
collective tally against the jaxpr psum count (the same quantity
tests/test_specramp.py asserts), Prometheus rendering, the /metrics
endpoint end-to-end, the profile CLI verb, and the enabled-vs-disabled
overhead guard.
"""

import json
import math
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry.metrics import (MetricsRegistry, SlidingWindow,
                                            percentile)

SMALL = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1}


@pytest.fixture(autouse=True)
def _telemetry_enabled():
    """Tests assume the default-on switch; restore whatever state the
    process was in afterwards."""
    was = telemetry.enabled()
    telemetry.enable()
    yield
    (telemetry.enable if was else telemetry.disable)()


def _train_binary(n=400, trees=5, seed=0, extra=None):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 6)
    y = (X[:, 0] * 2 + X[:, 1] + 0.5 * rng.randn(n) > 0).astype(np.float64)
    p = {**SMALL, "objective": "binary", **(extra or {})}
    return lgb.train(p, lgb.Dataset(X, y, params=p), trees), X


# -- metrics primitives -----------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c", "help", labels=("who",))
    c.inc(2, who="a")
    c.inc(who="a")
    c.inc(who="b")
    assert c.value(who="a") == 3 and c.value(who="b") == 1
    g = reg.gauge("g")
    g.set(5)
    g.max(3)       # watermark keeps the larger value
    assert g.value() == 5
    g.max(9)
    assert g.value() == 9
    assert reg.counter("c", labels=("who",)) is c  # get-or-create


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x", labels=("a",))
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("x", labels=("b",))


def test_label_validation():
    reg = MetricsRegistry()
    c = reg.counter("c", labels=("model",))
    with pytest.raises(ValueError):
        c.inc(1)  # missing label
    with pytest.raises(ValueError):
        c.inc(1, model="m", extra="nope")


def test_sliding_window_wrap_and_percentile():
    w = SlidingWindow(capacity=8)
    for v in range(20):
        w.add(float(v))
    assert len(w) == 8
    assert w.count == 20 and w.total == sum(range(20))
    assert w.sorted_values() == [float(v) for v in range(12, 20)]
    assert w.percentile(0) == 12.0 and w.percentile(100) == 19.0
    # nearest-rank edge cases of the shared helper
    assert percentile([], 50) == 0.0
    assert percentile([3.0], 99) == 3.0


def test_percentile_is_shared_with_serve_stats():
    from lightgbm_tpu.serve import stats as serve_stats
    assert serve_stats.percentile is percentile


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hits", labels=("t",))
    h = reg.histogram("lat", labels=("t",), window=64)
    n_threads, n_ops = 8, 500
    barrier = threading.Barrier(n_threads)

    def worker(t):
        barrier.wait()
        for i in range(n_ops):
            c.inc(1, t=str(t % 2))
            h.observe(float(i), t=str(t % 2))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value(t="0") + c.value(t="1") == n_threads * n_ops
    total = sum(summ["count"] for _, summ in h.series())
    assert total == n_threads * n_ops


def test_model_stats_schema_unchanged():
    """ModelStats rebased on the registry must keep its /stats schema."""
    from lightgbm_tpu.serve.stats import ModelStats
    s = ModelStats()
    s.record_request(3)
    s.record_batch(3, 8, 1.5, recompiled=True)
    s.record_batch(5, 8, 2.5, recompiled=False)
    s.record_error()
    snap = s.snapshot()
    assert snap["requests"] == 1 and snap["rows"] == 8
    assert snap["batches"] == 2 and snap["recompiles"] == 1
    assert snap["errors"] == 1
    assert snap["bucket_histogram"] == {"8": 2}
    assert snap["latency_ms"]["window"] == 2
    assert snap["latency_ms"]["p50"] > 0
    # two anonymous ModelStats never alias each other's series
    s2 = ModelStats()
    assert s2.snapshot()["batches"] == 0


# -- spans ------------------------------------------------------------------

def test_span_annotates_without_tracer(monkeypatch):
    """The profiler's session is the switch: with the tracer disabled a
    span still enters a TraceAnnotation carrying its slash path, and the
    Python-side event list stays empty."""
    from lightgbm_tpu.telemetry import trace as ttrace
    assert not ttrace.global_tracer.enabled
    ttrace.global_tracer.clear()
    entered = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("enter", self.name))

        def __exit__(self, *exc):
            entered.append(("exit", self.name))

    monkeypatch.setattr(ttrace, "TraceAnnotation", FakeAnnotation)
    with telemetry.span("train/iter"):
        assert ttrace.in_span()
        with telemetry.span("grow"):
            pass
    assert not ttrace.in_span()
    assert entered == [("enter", "train/iter"), ("enter", "train/iter/grow"),
                       ("exit", "train/iter/grow"), ("exit", "train/iter")]
    assert ttrace.global_tracer.events() == []


def test_span_cost_without_session():
    """No profiler session, tracer off: a span is a TraceMe that does
    nothing.  Expect about a microsecond; the ceiling is generous."""
    from lightgbm_tpu.telemetry import trace as ttrace
    assert not ttrace.global_tracer.enabled
    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        with telemetry.span("probe"):
            pass
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    assert per_span_us < 50, per_span_us


def test_timed_span_adds_host_seconds():
    store = {}
    with telemetry.timed_span(store, "k", "probe/a"):
        time.sleep(0.002)
    with telemetry.timed_span(store, "k", "probe/b"):
        pass
    assert store["k"] >= 0.002 and list(store) == ["k"]


def test_span_nesting_and_chrome_export(tmp_path):
    tr = telemetry.global_tracer
    tr.enable()
    tr.clear()
    try:
        with telemetry.span("tree"):
            with telemetry.span("wave"):
                time.sleep(0.002)
            with telemetry.span("psum"):
                pass
        names = [e["name"] for e in tr.events()]
        assert names == ["tree/wave", "tree/psum", "tree"]
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in tr.events())
        out = tmp_path / "trace.json"
        assert tr.export_chrome_trace(str(out)) == 3
        doc = json.loads(out.read_text())
        assert len(doc["traceEvents"]) == 3
    finally:
        tr.disable()
        tr.clear()


# -- TrainRecord through real training --------------------------------------

def test_train_record_accumulates():
    bst, _ = _train_binary(trees=5)
    rec = bst.train_record
    assert rec is telemetry.last_train_record()
    snap = rec.snapshot()
    assert snap["schema"] == "train-record-v1"
    assert snap["num_trees"] == 5
    assert len(snap["trees"]) == 5
    assert [r["iteration"] for r in snap["trees"]] == list(range(5))
    for ph in ("gradients", "grow", "record"):
        assert snap["phase_seconds"].get(ph, 0) > 0
        assert snap["phase_calls"][ph] == 5
    assert snap["meta"]["objective"] == "binary"
    assert all(r["num_leaves"] >= 1 for r in snap["trees"])


def test_train_record_wave_hist_passes():
    """Through the full Booster path on the wave grower, the exported
    per-tree hist_passes is the GrownTree counter the endgame tests
    assert: every tree's pass log is that long."""
    bst, _ = _train_binary(n=600, trees=3,
                           extra={"tree_grow_mode": "wave",
                                  "num_leaves": 13})
    snap = bst.train_record.snapshot()
    hp = [r["hist_passes"] for r in snap["trees"]]
    assert len(hp) == 3
    assert all(p >= 1 for p in hp), hp  # wave grower tracks passes
    assert hp == [len(r["passes"]) for r in snap["trees"]]
    assert snap["hist_passes_total"] == sum(hp)
    assert snap["hist_passes_last"] == hp[-1]


class _TickClock:
    """``time`` for the two telemetry modules: every read is a second on."""

    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        self.now += 1.0
        return self.now


@pytest.mark.parametrize("classes", [1, 3], ids=["binary", "multiclass"])
def test_tree_clock_with_a_patched_clock(monkeypatch, classes):
    """``done_s`` is stamped by the NEXT iteration, after its one wait:
    monotone, None for the newest tree; an iteration's wait and dispatch
    fit in its tree's period; every class row of an iteration carries the
    same three."""
    from lightgbm_tpu.telemetry import trace as ttrace
    from lightgbm_tpu.telemetry import train_record as tr
    rng = np.random.RandomState(1)
    X = rng.randn(600, 6)
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0.3)).astype(np.float64)
    p = {**SMALL, "tree_grow_mode": "wave", **(
        {"objective": "multiclass", "num_class": 3} if classes == 3
        else {"objective": "binary"})}
    if classes == 1:
        y = (y > 0).astype(np.float64)
    train_set = lgb.Dataset(X, y, params=p)
    train_set.construct()
    clock = _TickClock()
    monkeypatch.setattr(ttrace, "time", clock)
    monkeypatch.setattr(tr, "time", clock)
    bst = lgb.Booster(params=p, train_set=train_set)
    for _ in range(5):
        bst.update()
    snap = bst.train_record.snapshot()
    rows = snap["trees"]
    assert len(rows) == 5 * classes
    by_iter = [rows[i * classes:(i + 1) * classes] for i in range(5)]
    for group in by_iter:
        assert len({(r["done_s"], r["wait_s"], r["dispatch_s"])
                    for r in group}) == 1
    it = [g[0] for g in by_iter]
    assert it[-1]["done_s"] is None
    done = [r["done_s"] for r in it[:-1]]
    assert all(float(d).is_integer() for d in done)      # the patched clock
    assert all(b > a for a, b in zip(done, done[1:]))
    assert it[0]["wait_s"] == 0.0                        # nothing to wait for
    for before, row in zip(it[:-2], it[1:-1]):
        period = row["done_s"] - before["done_s"]
        assert row["wait_s"] > 0 and row["dispatch_s"] > 0
        assert row["wait_s"] + row["dispatch_s"] <= period
    # the wait has a span and a phase of its own, once a later iteration
    assert snap["phase_calls"]["wait_prev"] == 4
    assert sum(r["wait_s"] for r in it) == snap["phase_seconds"]["wait_prev"]


def test_setup_seconds_in_train_record():
    """The set-up's host seconds by phase, the dataset's copied in; the
    two binning halves fit inside the wall time around construct()."""
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 6)
    y = (X[:, 0] > 0).astype(np.float64)
    p = {**SMALL, "objective": "binary"}
    ds = lgb.Dataset(X, y, params=p)
    t0 = time.perf_counter()
    ds.construct()
    construct_s = time.perf_counter() - t0
    bst = lgb.Booster(params=p, train_set=ds)
    for _ in range(3):
        bst.update()
    snap = bst.train_record.snapshot()
    secs = snap["setup_seconds"]
    for key in ("to_float64", "bin_find", "bin_matrix", "upload", "layout",
                "first_update", "jax_trace_lower", "compile_or_load"):
        assert key in secs and secs[key] >= 0, (key, secs)
    assert 0 < secs["bin_find"] + secs["bin_matrix"] <= construct_s
    # the first update carries the compile; the later two are phases only
    assert secs["first_update"] > 0
    assert snap["phase_calls"]["grow"] == 3
    # a second snapshot reads the same set-up: nothing accumulates twice
    assert bst.train_record.snapshot()["setup_seconds"]["bin_find"] == \
        secs["bin_find"]


def test_compile_seconds_are_a_union_not_a_sum():
    """An inner jit's trace event ends inside its caller's: the kind's
    seconds are the union of the intervals."""
    from lightgbm_tpu.telemetry import train_record as tr
    assert tr._union_seconds([(0.0, 10.0), (2.0, 3.0), (9.0, 12.0),
                              (20.0, 21.0)]) == pytest.approx(13.0)
    assert tr._union_seconds([]) == 0.0
    rec = tr.TrainRecord()
    import jax
    import jax.numpy as jnp

    @jax.jit
    def outer(x):
        return jnp.cumsum(x * 2.0 + float(time.perf_counter() % 1))  # fresh trace

    t0 = time.perf_counter()
    outer(jnp.arange(7.0)).block_until_ready()
    wall = time.perf_counter() - t0
    secs = rec.snapshot()["setup_seconds"]
    assert 0 < secs["jax_trace_lower"] <= wall
    assert 0 < secs["compile_or_load"] <= wall
    assert secs["jax_trace_lower"] + secs["compile_or_load"] <= wall


@pytest.mark.parametrize("quantized", [True, False], ids=["q8", "exact"])
def test_pass_kinds_sum_to_hist_passes(quantized):
    """1 + wave_passes + endgame_passes == hist_passes for every tree of
    the wave grower, and the ramp commits at most W-1 splits."""
    bst, _ = _train_binary(n=3000, trees=3,
                           extra={"tree_grow_mode": "wave", "num_leaves": 63,
                                  "use_quantized_grad": quantized,
                                  "min_data_in_leaf": 2})
    rows = bst.train_record.snapshot()["trees"]
    assert len(rows) == 3
    for r in rows:
        assert r["hist_passes"] >= 1
        assert 1 + r["wave_passes"] + r["endgame_passes"] == r["hist_passes"], r
        assert r["wave_passes"] >= 0 and r["endgame_passes"] >= 0
        assert 0 <= r["ramp_committed"] <= 41
    # 63 leaves is past 2W on the exact grower (W=25)... at least one kind ran
    assert any(r["wave_passes"] + r["endgame_passes"] > 0 for r in rows)


def test_other_growers_report_zero_pass_kinds():
    bst, _ = _train_binary(trees=2)      # partition-ordered serial grower
    for r in bst.train_record.snapshot()["trees"]:
        assert (r["hist_passes"], r["wave_passes"], r["endgame_passes"],
                r["ramp_committed"]) == (0, 0, 0, 0)


def test_training_bit_identical_with_telemetry_disabled():
    """The acceptance contract: telemetry only observes — the grown
    model must be bit-for-bit the same with telemetry on and off."""
    telemetry.disable()
    try:
        bst_off, X = _train_binary(trees=4, seed=3)
        txt_off = bst_off.model_to_string()
        pred_off = bst_off.predict(X[:50], raw_score=True)
    finally:
        telemetry.enable()
    bst_on, X2 = _train_binary(trees=4, seed=3)
    assert bst_on.model_to_string() == txt_off
    np.testing.assert_array_equal(
        bst_on.predict(X2[:50], raw_score=True), pred_off)
    # and the disabled run recorded nothing: no row, no clock, no phase
    off = bst_off.train_record.snapshot()
    assert off["num_trees"] == 0 and off["trees"] == []
    assert off["phase_seconds"] == {}
    on = bst_on.train_record.snapshot()
    assert on["num_trees"] == 4
    assert all({"done_s", "wait_s", "dispatch_s", "passes"} <= set(r)
               for r in on["trees"])


# -- collective tally vs the traced program ---------------------------------

def _mk_dp_data(n_raw):
    from lightgbm_tpu.ops.histogram_pallas import pad_rows
    rng = np.random.RandomState(0)
    n = pad_rows(n_raw)
    bins = rng.randint(0, 63, (6, n)).astype(np.uint8)
    y = ((bins[0] > 30).astype(np.float32))
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    mask = np.ones(n, np.float32)
    mask[n_raw:] = 0.0
    return bins, grad, hess, mask, n


def _trace_dp_grow(spec, wave=4):
    """Trace (don't run) the DP wave grower; the psum counting rides the
    shared analysis.ir walker (tests/test_specramp.py counts the same
    quantity through the same API)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis import ir
    from lightgbm_tpu.learner.wave import make_wave_grow_fn
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy
    from lightgbm_tpu.parallel.mesh import get_mesh, shard_wave_grower
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    grow = make_wave_grow_fn(
        num_leaves=13, num_features=6, max_bins=64, max_depth=0,
        split_params=sp, hist_impl="pallas", any_cat=False, interpret=True,
        jit=False, wave_size=wave, quantized=True, stochastic=False,
        spec_ramp=spec, spec_tol=0.02,
        strategy=WaveDPStrategy(ax, nshards=8))
    wrapped = shard_wave_grower(
        lambda X_T, g, h, m, nb, ic, hn, mono, cp, fm: grow(
            X_T, g, h, m, nb, ic, hn, mono, cp, (), fm), mesh, ax)
    bins, grad, hess, mask, n = _mk_dp_data(8 * 4096 - 100)
    nb = jnp.full((6,), 64, jnp.int32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask), nb, jnp.zeros((6,), bool),
            jnp.zeros((6,), bool), jnp.zeros((6,), jnp.int32),
            jnp.zeros((6,), jnp.float32), jnp.ones((6,), bool))
    before = telemetry.collectives_snapshot().get(
        "data_parallel/wave/hist_psum", {"count": 0})["count"]
    n_psum = ir.count_primitive(
        ir.trace(lambda *a: wrapped(*a), *args), "psum")
    after = telemetry.collectives_snapshot().get(
        "data_parallel/wave/hist_psum", {"count": 0})["count"]
    return after - before, n_psum


def test_collective_tally_matches_traced_psum_delta():
    """The telemetry tally at the WaveDPStrategy.reduce_hist site must
    report the SAME spec-ramp collective budget test_specramp.py asserts
    on the jaxpr: spec-on minus spec-off == ceil(log2(W)) extra
    histogram psums per tree."""
    w = 4
    tally_off, n_off = _trace_dp_grow(False, wave=w)
    tally_on, n_on = _trace_dp_grow(True, wave=w)
    assert tally_off >= 1
    assert tally_on - tally_off == math.ceil(math.log2(w))
    # the tally site is the histogram psum: its per-trace count moves
    # exactly with the program's psum op count
    assert (tally_on - tally_off) == (n_on - n_off)
    # and the recorded bytes are the histogram batch operand size
    rec = telemetry.collectives_snapshot()["data_parallel/wave/hist_psum"]
    assert rec["op"] == "psum" and rec["bytes"] > 0


# -- export + /metrics ------------------------------------------------------

def test_prometheus_render_covers_registry_and_train_record():
    bst, X = _train_binary(trees=3, seed=5)
    txt = telemetry.render_prometheus()
    assert "# TYPE lgbm_tpu_train_trees_total counter" in txt
    assert "lgbm_tpu_train_trees_total 3" in txt
    assert 'lgbm_tpu_train_phase_seconds_total{phase="grow"}' in txt
    doc = telemetry.render_json()
    assert doc["schema"] == "telemetry-snapshot-v1"
    assert doc["train_record"]["num_trees"] == 3


def test_metrics_endpoint_e2e():
    """Acceptance: /metrics serves Prometheus text covering both the
    serving counters and the last training run's TrainRecord."""
    import http.client
    from lightgbm_tpu.serve import ModelRegistry, PredictionServer
    bst, X = _train_binary(trees=4, seed=7)
    reg = ModelRegistry()
    reg.load("telem_model", bst, warmup=False)
    srv = PredictionServer(reg, port=0, batching=False).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        conn.request("POST", "/predict",
                     json.dumps({"rows": X[:3].tolist()}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 200
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/plain")
        body = resp.read().decode()
        # serving counters, labeled by model
        assert 'lgbm_tpu_serve_requests_total{model="telem_model"} 1' \
            in body
        assert 'lgbm_tpu_serve_rows_total{model="telem_model"} 3' in body
        assert 'lgbm_tpu_serve_batch_latency_ms_p50' \
               '{model="telem_model"}' in body
        # the last training run's record
        assert "lgbm_tpu_train_trees_total 4" in body
        assert 'lgbm_tpu_train_phase_seconds_total{phase="grow"}' in body
    finally:
        srv.shutdown()


def test_profile_cli_verb(tmp_path):
    """`python -m lightgbm_tpu profile` trains, then dumps telemetry +
    host spans (device capture disabled for speed)."""
    from lightgbm_tpu.cli import main as cli_main
    rng = np.random.RandomState(0)
    X = rng.randn(200, 4)
    y = (X[:, 0] > 0).astype(np.float64)
    data = tmp_path / "train.csv"
    np.savetxt(data, np.column_stack([y, X]), delimiter=",", fmt="%.6f")
    prof = tmp_path / "prof"
    rc = cli_main([
        "profile", f"data={data}", "task=train", "objective=binary",
        "num_leaves=4", "min_data_in_leaf=5", "num_iterations=3",
        "header=false", "verbosity=-1",
        f"output_model={tmp_path / 'model.txt'}",
        f"profile_dir={prof}", "jax_trace=0",
    ])
    assert rc == 0
    # the verb enables the tracer process-wide; undo for the rest of the
    # suite
    telemetry.global_tracer.disable()
    telemetry.global_tracer.clear()
    dump = json.loads((prof / "telemetry.json").read_text())
    assert dump["schema"] == "telemetry-snapshot-v1"
    assert dump["train_record"]["num_trees"] == 3
    spans = json.loads((prof / "host_spans.json").read_text())
    assert any(e["name"].startswith("train/")
               for e in spans["traceEvents"])
    assert (tmp_path / "model.txt").exists()


# -- overhead guard ---------------------------------------------------------

def test_telemetry_overhead_guard():
    """CI satellite: telemetry-enabled training must stay within a
    generous wall-time ratio of disabled training (it only appends to
    host-side lists and reads perf_counter)."""
    def timed(trees=6, seed=11):
        t0 = time.perf_counter()
        _train_binary(n=1000, trees=trees, seed=seed)
        return time.perf_counter() - t0

    timed(trees=2)          # warm compile caches out of the measurement
    telemetry.disable()
    try:
        t_off = timed()
    finally:
        telemetry.enable()
    t_on = timed()
    # generous: the accumulation is micro-seconds per tree; anything
    # near the ratio would be a real regression, not timing noise
    assert t_on <= 3.0 * t_off + 1.0, (t_on, t_off)
