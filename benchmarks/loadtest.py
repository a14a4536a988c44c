"""Synthetic-load SLO harness: drive the real HTTP serving tier, judge
it from its own telemetry.

Closes the observability loop the SLO engine opens: train a small
model, start the REAL :class:`PredictionServer` (sockets, JSON, micro
batcher, admission control), drive a ladder of synthetic load rungs
through ``serve/loadgen.py`` (open loop at target QPS or closed loop at
the ceiling, request shapes mixed over the SHAPE_BUCKETS ladder), and
render a pass/breach verdict computed SOLELY from ``/metrics`` and
``/slo`` scrapes — the client-side numbers ride along for context but
never decide anything, so the harness proves the telemetry an operator
would actually page on.

Artifacts: an ``slo-report.json`` (verdict + the /slo payload + the
slowest-request exemplars + per-bucket p50/p99/queue/device split) and
a bench-matrix-v1 record (rows_per_sec / qps / p99_ms rows) that
``scripts/bench_regression.py`` diffs across nightly rounds exactly
like iters/s.

    python benchmarks/loadtest.py [--json out.json] \
        [--slo-report slo-report.json]

Env knobs: LOAD_LADDER ("closed" and/or comma QPS list, e.g.
"10,25,closed"), LOAD_DURATION (s/rung), LOAD_WORKERS, LOAD_FEATURES,
LOAD_TREES, LOAD_LEAVES, LOAD_BUCKETS ("4096:0.9,512:0.1" rows:weight
mix), LOAD_ARRIVAL (uniform|poisson), LOAD_TARGET_ROWS_S (pass floor,
default 1e5), LOAD_P99_MS (re-declares the serve/latency_p99 threshold
for this env), LOAD_MAX_QUEUE_ROWS (admission bound; 0 = unbounded).

``--fleet-chaos`` switches to the fleet-resilience rung: a multi-worker
``FleetSupervisor`` serves open-loop loadgen traffic while the chaos
layer's ``serve_crash_after_n`` hard-kills one worker mid-run; the
verdict — worker crashed AND the fleet recovered to full strength AND
the availability SLO is met after the recovery window AND every client
request reached a terminal outcome — is computed solely from the fleet
``/metrics`` + ``/slo`` scrapes (env knobs: FLEET_WORKERS,
FLEET_DURATION, FLEET_QPS, FLEET_CRASH_AFTER, FLEET_RECOVERY_S).
This rung is CPU ONLY: the launcher has touched JAX by the time it
spawns, and a chip belongs to one process at a time, so the workers are
pinned to ``JAX_PLATFORMS=cpu`` — it judges supervision and recovery,
never device speed.

``--refresh`` runs the model-refresh-under-load rung: the same
per-round updates are deployed to a live server as wire deltas
(``POST /models/<name>/delta``, in-envelope dense splices) and as full
hot-swaps (``POST /models`` reload) while open-loop traffic flows; the
verdict requires the delta lane to reach the head round with ZERO dense
recompiles and both lanes to stay 5xx-free, and the per-lane p99 +
recompile counts land in the bench matrix (env knobs: REFRESH_DURATION,
REFRESH_QPS, REFRESH_BASE_ROUNDS, REFRESH_ROUNDS, REFRESH_SHARD).

``--zoo`` runs the multi-tenant model-zoo rung: zipf-distributed
traffic over 16 same-shape tenants is served twice by the real HTTP
server — once through the zoo's batched cross-model stacked dispatch
and once with stacking off (per-model batchers) — and the verdict
requires the stacked lane to deliver >= 2x rows/s OR >= 4x fewer MXU
launches per 1k requests, with every cold load-on-miss counted and its
p99 reported (env knobs: ZOO_MODELS, ZOO_DURATION, ZOO_THREADS,
ZOO_ROWS, ZOO_ZIPF, ZOO_MAX_WAIT_MS).

``--explain`` runs the explanation-serving rung: closed-loop
``POST /explain`` traffic with interleaved ``/predict`` requests on the
same model; the verdict requires a 5xx-free explain response counter,
the ``serve/explain_latency_p99`` SLO met on the /slo scrape, ZERO
dense->walk fallback batches (a silent host-walk regression fails the
rung even if latency survives), and the untouched predict lane to stay
5xx-free (env knobs: EXPLAIN_DURATION, EXPLAIN_THREADS, EXPLAIN_ROWS,
EXPLAIN_FEATURES, EXPLAIN_TREES, EXPLAIN_LEAVES, EXPLAIN_PREDICT_EVERY,
EXPLAIN_P99_MS).

Exit code: 0 on pass, 1 on breach/underrun — CI runs all modes
blocking, next to the chaos step.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _git_sha():
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or None
    except Exception:
        return None


def _train_model(trees: int, leaves: int, features: int, tmp: str) -> str:
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.randn(2000, features).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.randn(2000) > 0).astype(np.float64)
    p = {"objective": "binary", "num_leaves": leaves, "verbosity": -1}
    bst = lgb.train(p, lgb.Dataset(X, y, params=p), trees)
    path = os.path.join(tmp, "loadtest_model.txt")
    bst.save_model(path)
    return path


def _parse_bucket_mix(spec: str):
    mix = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if ":" in tok:
            rows, w = tok.split(":", 1)
            mix[int(rows)] = float(w)
        else:
            mix[int(tok)] = 1.0
    return mix or {4096: 1.0}


def _bucket_latency(parsed, model: str):
    """Per-bucket p50/p99 + queue/device split from one /metrics parse."""
    from lightgbm_tpu.serve.loadgen import metric_sum
    out = {}
    for lbl, val in parsed.get("lgbm_tpu_serve_request_latency_ms_p99", ()):
        if lbl.get("model") != model:
            continue
        b = lbl.get("bucket", "?")
        out[b] = {
            "p99_ms": val,
            "p50_ms": metric_sum(
                parsed, "lgbm_tpu_serve_request_latency_ms_p50",
                model=model, bucket=b),
            "queue_wait_p50_ms": metric_sum(
                parsed, "lgbm_tpu_serve_queue_wait_ms_p50",
                model=model, bucket=b),
            "device_p50_ms": metric_sum(
                parsed, "lgbm_tpu_serve_device_ms_p50",
                model=model, bucket=b),
            "requests": metric_sum(
                parsed, "lgbm_tpu_serve_request_latency_ms_count",
                model=model, bucket=b),
        }
    return out


def run_loadtest(ladder=("closed",), duration_s: float = 5.0,
                 workers: int = 3, features: int = 4, trees: int = 20,
                 leaves: int = 15, bucket_mix=None, arrival: str = "uniform",
                 target_rows_per_s: float = 1e5,
                 p99_threshold_ms: float = 0.0,
                 max_queue_rows: int = 0,
                 scrape_interval_s: float = 1.0):
    """Run the ladder against a fresh in-process server; return the
    verdict report.  Every pass/breach number is read back from the
    server's own /metrics and /slo endpoints."""
    from lightgbm_tpu.serve.loadgen import (LoadGenerator, LoadSpec,
                                            metric_sum, parse_prometheus,
                                            scrape_json, scrape_metrics)
    from lightgbm_tpu.serve.registry import ModelRegistry
    from lightgbm_tpu.serve.server import PredictionServer
    from lightgbm_tpu.telemetry.slo import set_latency_threshold
    from lightgbm_tpu.utils.backend import default_backend
    from lightgbm_tpu.utils.log import set_verbosity

    backend = default_backend()
    set_verbosity(-1)
    bucket_mix = dict(bucket_mix or {4096: 1.0})
    if p99_threshold_ms and p99_threshold_ms > 0:
        set_latency_threshold("serve/latency_p99", p99_threshold_ms)

    with tempfile.TemporaryDirectory() as tmp:
        model_file = _train_model(trees, leaves, features, tmp)
        registry = ModelRegistry()
        # a fresh engine: the harness judges THIS run's burn, not
        # whatever the process-wide engine sampled before it
        from lightgbm_tpu.telemetry.slo import SloEngine
        srv = PredictionServer(registry, port=0,
                               max_queue_rows=int(max_queue_rows),
                               slo_engine=SloEngine()).start()
        host, port = srv.host, srv.port
        rungs = []
        try:
            for rung in ladder:
                qps = 0.0 if str(rung).strip() == "closed" else float(rung)
                label = "closed" if qps <= 0 else f"qps{qps:g}"
                # one registry name per rung: the latency windows are
                # cumulative per (model, bucket) series, so a shared
                # name would contaminate each rung's p99 with the
                # previous rungs' samples
                model_name = f"loadtest-{label}"
                registry.load(model_name, model_file, warmup=True)
                spec = LoadSpec(duration_s=duration_s, target_qps=qps,
                                workers=workers, features=features,
                                bucket_mix=bucket_mix, arrival=arrival,
                                model=model_name)
                gen = LoadGenerator(host, port, spec)

                # periodic /slo evaluations while the load flows, so the
                # burn windows sample DURING the rung, not just after it
                stop = threading.Event()

                def scraper():
                    while not stop.wait(scrape_interval_s):
                        try:
                            scrape_json(host, port, "/slo")
                        except Exception:
                            pass

                before = parse_prometheus(scrape_metrics(host, port))
                t0 = time.perf_counter()
                sc = threading.Thread(target=scraper, daemon=True)
                sc.start()
                client = gen.run()
                stop.set()
                sc.join(2.0)
                after = parse_prometheus(scrape_metrics(host, port))
                elapsed = time.perf_counter() - t0
                slo_rep = scrape_json(host, port, "/slo")

                def delta(name, **labels):
                    return metric_sum(after, name, **labels) - \
                        metric_sum(before, name, **labels)

                rows_served = delta("lgbm_tpu_serve_rows_total",
                                    model=model_name)
                reqs = delta("lgbm_tpu_serve_requests_total",
                             model=model_name)
                resp_total = delta(
                    "lgbm_tpu_serve_predict_responses_total")
                resp_5xx = sum(
                    delta("lgbm_tpu_serve_predict_responses_total", code=c)
                    for c in ("500", "503", "504"))
                rungs.append({
                    "label": label,
                    "config": {"target_qps": qps, "duration_s": duration_s,
                               "workers": workers, "features": features,
                               "bucket_mix": {str(k): v for k, v in
                                              sorted(bucket_mix.items())},
                               "arrival": arrival, "backend": backend,
                               "max_queue_rows": int(max_queue_rows)},
                    # server-side truth (the verdict inputs).
                    # Availability reads the /predict-only response
                    # counter — the harness's own /slo+/metrics scrape
                    # 200s must not dilute a shed's severity
                    "rows_per_sec": round(rows_served / elapsed, 1),
                    "qps": round(reqs / elapsed, 2),
                    "availability": round(
                        1.0 - (resp_5xx / resp_total if resp_total
                               else 0.0), 6),
                    "shed": delta("lgbm_tpu_requests_shed_total",
                                  model=model_name),
                    "per_bucket": _bucket_latency(after, model_name),
                    "slo": slo_rep,
                    # client-side context (never judged)
                    "client": client.summary(),
                })
        finally:
            srv.shutdown()

    best = max(rungs, key=lambda r: r["rows_per_sec"]) if rungs else None
    slo_ok = all(r["slo"].get("ok", False) for r in rungs)
    rows_ok = best is not None and \
        best["rows_per_sec"] >= float(target_rows_per_s)
    return {
        "schema": "loadtest-slo-report-v1",
        "git_sha": _git_sha(),
        "backend": backend,
        "verdict": "pass" if (slo_ok and rows_ok) else "breach",
        "slo_ok": slo_ok,
        "rows_ok": rows_ok,
        "target_rows_per_s": float(target_rows_per_s),
        "peak_rows_per_sec": best["rows_per_sec"] if best else 0.0,
        "verdict_source": "/metrics + /slo scrapes only",
        "rungs": rungs,
    }


def run_fleet_chaos(workers: int = 2, duration_s: float = 8.0,
                    qps: float = 30.0, crash_after: int = 40,
                    recovery_window_s: float = 10.0,
                    features: int = 4, trees: int = 20,
                    leaves: int = 15, bucket_rows: int = 8,
                    scrape_interval_s: float = 0.5):
    """Fleet chaos-under-load smoke: start a supervised worker fleet,
    arm worker 0 with ``serve_crash_after_n`` (its FIRST incarnation
    hard-kills itself after N /predict requests — the replacement boots
    clean), drive open-loop traffic through the dispatcher, then judge
    recovery exclusively from fleet ``/metrics`` + ``/slo`` scrapes."""
    from lightgbm_tpu.serve.fleet import FleetSupervisor
    from lightgbm_tpu.serve.loadgen import (LoadGenerator, LoadSpec,
                                            metric_sum, parse_prometheus,
                                            scrape_json, scrape_metrics)
    from lightgbm_tpu.utils.backend import default_backend
    from lightgbm_tpu.utils.log import set_verbosity

    backend = default_backend()
    set_verbosity(-1)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    with tempfile.TemporaryDirectory() as tmp:
        model_file = _train_model(trees, leaves, features, tmp)
        fleet = FleetSupervisor(
            [model_file], workers=int(workers),
            # one process per chip: spawned workers stay off the device
            worker_env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": repo},
            worker_args={"warmup": "0", "max_wait_ms": "0.5"},
            first_spawn_env={0: {"LGBM_TPU_FAULTS":
                                 f"serve_crash_after_n={crash_after}"}},
            probe_interval_s=0.25, backoff_base_s=0.2,
            backoff_max_s=1.0, breaker_halfopen_s=1.0,
            startup_timeout_s=300.0,
            run_dir=os.path.join(tmp, "fleet"))
        fleet.start()
        host, port = fleet.host, fleet.port
        try:
            spec = LoadSpec(duration_s=duration_s, target_qps=qps,
                            workers=2, features=features,
                            bucket_mix={int(bucket_rows): 1.0}, seed=1,
                            timeout_s=10.0)
            gen = LoadGenerator(host, port, spec)

            stop = threading.Event()

            def scraper():
                # burn windows sample DURING the chaos, not just after
                while not stop.wait(scrape_interval_s):
                    try:
                        scrape_json(host, port, "/slo")
                    except Exception:
                        pass

            sc = threading.Thread(target=scraper, daemon=True)
            sc.start()
            client = gen.run()
            stop.set()
            sc.join(2.0)

            # recovery window: the supervisor restores full strength
            recovered = False
            deadline = time.perf_counter() + recovery_window_s
            while time.perf_counter() < deadline:
                parsed = parse_prometheus(scrape_metrics(host, port))
                if metric_sum(parsed,
                              "lgbm_tpu_fleet_workers_alive") == workers:
                    recovered = True
                    break
                time.sleep(0.25)

            parsed = parse_prometheus(scrape_metrics(host, port))
            slo_rep = scrape_json(host, port, "/slo")
            restarts = metric_sum(parsed, "lgbm_tpu_fleet_restarts_total")
            retries = metric_sum(parsed, "lgbm_tpu_fleet_retries_total")
            quarantined = metric_sum(parsed,
                                     "lgbm_tpu_fleet_workers_quarantined")
            total = metric_sum(parsed,
                               "lgbm_tpu_serve_predict_responses_total")
            bad = sum(metric_sum(parsed,
                                 "lgbm_tpu_serve_predict_responses_total",
                                 code=c)
                      for c in ("500", "502", "503", "504"))
        finally:
            fleet.shutdown()

    availability = 1.0 - (bad / total) if total else 0.0
    # terminality must be FALSIFIABLE: the sent-vs-outcome ledger
    # balances by construction of the generator loop, so the real
    # assertion is the wall clock — a hung request blocks its
    # generator thread past the per-connection socket timeout, so a
    # run whose elapsed time blows duration + timeout + slack had a
    # request with no terminal outcome inside the client's patience
    ledger_ok = (sum(client.by_code.values()) + client.connect_errors
                 == client.requests_sent)
    no_hang = client.elapsed_s <= duration_s + spec.timeout_s + 5.0
    all_terminal = ledger_ok and no_hang
    crashed = restarts >= 1
    slo_ok = bool(slo_rep.get("ok"))
    verdict = "pass" if (crashed and recovered and slo_ok and
                         all_terminal and total > 0) else "breach"
    return {
        "schema": "fleet-chaos-report-v1",
        "git_sha": _git_sha(),
        "backend": backend,
        "verdict": verdict,
        "verdict_source": "fleet /metrics + /slo scrapes only",
        "config": {"workers": int(workers), "duration_s": duration_s,
                   "target_qps": qps, "crash_after": int(crash_after),
                   "recovery_window_s": recovery_window_s,
                   "bucket_rows": int(bucket_rows)},
        "crashed": crashed,
        "recovered": recovered,
        "slo_ok": slo_ok,
        "all_requests_terminal": all_terminal,
        "availability": round(availability, 6),
        "fleet_restarts_total": restarts,
        "fleet_retries_total": retries,
        "fleet_workers_quarantined": quarantined,
        "qps": round(client.achieved_qps, 2),
        "slo": slo_rep,
        "client": client.summary(),
    }


def _post_json(host: str, port: int, path: str, payload: dict,
               timeout: float = 60.0):
    import http.client
    body = json.dumps(payload).encode()
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path, body, {
            "Content-Type": "application/json",
            "Content-Length": str(len(body))})
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw)
        except ValueError:
            return resp.status, {}
    finally:
        conn.close()


def run_refresh_under_load(duration_s: float = 6.0, qps: float = 40.0,
                           features: int = 6, base_rounds: int = 4,
                           refresh_rounds: int = 4, shard: int = 16,
                           leaves: int = 15, bucket_rows: int = 8,
                           workers: int = 2):
    """Model-refresh-under-load rung: the same per-round updates are
    deployed to a live server two ways — appended as wire deltas
    (``POST /models/<name>/delta``, in-envelope dense splices) and as
    full-model hot-swaps (``POST /models`` reload) — while open-loop
    traffic flows.  Reports deploy-attributable p99 and the recompile
    count per mode; the verdict requires the delta lane to reach the
    head round with ZERO dense recompiles and both lanes to stay 5xx-
    free, proving live refresh is latency-neutral where the old swap
    path pays a re-lower per round."""
    import base64

    import lightgbm_tpu as lgb
    from lightgbm_tpu.models.model_text import model_to_string
    from lightgbm_tpu.publish.delta import DeltaJournal
    from lightgbm_tpu.serve.loadgen import (LoadGenerator, LoadSpec,
                                            metric_sum, parse_prometheus,
                                            scrape_metrics)
    from lightgbm_tpu.serve.registry import ModelRegistry
    from lightgbm_tpu.serve.server import PredictionServer
    from lightgbm_tpu.utils.backend import default_backend
    from lightgbm_tpu.utils.log import set_verbosity

    backend = default_backend()
    set_verbosity(-1)
    total = base_rounds + refresh_rounds

    with tempfile.TemporaryDirectory() as tmp:
        rng = np.random.RandomState(0)
        X = rng.randn(2000, features).astype(np.float32)
        y = (X[:, 0] + 0.3 * rng.randn(2000) > 0).astype(np.float64)
        p = {"objective": "binary", "num_leaves": leaves, "verbosity": -1}
        bst = lgb.train(p, lgb.Dataset(X, y, params=p), total)

        # journal: BASE at base_rounds, one delta per later round; the
        # full-swap lane replays the same rounds as folded text files
        j = DeltaJournal(os.path.join(tmp, "journal"))
        j.write_base(model_to_string(bst._gbdt, num_iteration=base_rounds),
                     base_rounds)
        for r in range(base_rounds + 1, total + 1):
            j.append_delta(model_to_string(bst._gbdt, start_iteration=r - 1,
                                           num_iteration=1), r)
        base_path, base_round = j.base_entry()
        records = list(j.records_after(base_round))
        folded = {}
        for r in range(base_rounds + 1, total + 1):
            path = os.path.join(tmp, f"folded_{r}.txt")
            with open(path, "w") as fh:
                fh.write(model_to_string(bst._gbdt, num_iteration=r))
            folded[r] = path

        registry = ModelRegistry()
        from lightgbm_tpu.telemetry.slo import SloEngine
        srv = PredictionServer(registry, port=0, max_wait_ms=0.5,
                               slo_engine=SloEngine()).start()
        host, port = srv.host, srv.port
        lanes = []
        try:
            for mode in ("delta", "full"):
                name = f"refresh-{mode}"
                # force the dense compiler: the rung measures the dense
                # tree-axis splice, which the CPU cost model would
                # otherwise trade away for walk mode on small models
                registry.load(name, base_path, warmup=True,
                              shard=int(shard), compiler="dense")
                pred0 = registry.get(name)
                r0 = pred0.stats.snapshot()["recompiles"]
                spec = LoadSpec(duration_s=duration_s, target_qps=qps,
                                workers=int(workers), features=features,
                                bucket_mix={int(bucket_rows): 1.0},
                                model=name, seed=2)
                gen = LoadGenerator(host, port, spec)
                interval = duration_s / (len(records) + 1)
                applies = []

                def refresher():
                    # one refresh per interval, spread across the rung
                    for i, rec in enumerate(records):
                        time.sleep(interval)
                        rnd = rec.round
                        try:
                            if mode == "delta":
                                b64 = base64.b64encode(
                                    rec.to_bytes()).decode()
                                code, body = _post_json(
                                    host, port, f"/models/{name}/delta",
                                    {"record_b64": b64})
                            else:
                                code, body = _post_json(
                                    host, port, "/models",
                                    {"name": name, "file": folded[rnd],
                                     "shard": int(shard),
                                     "compiler": "dense"})
                            applies.append(
                                {"round": rnd, "status": code,
                                 "mode": body.get("mode", mode)})
                        except Exception as exc:
                            applies.append({"round": rnd, "status": 0,
                                            "mode": f"error:{exc}"})

                before = parse_prometheus(scrape_metrics(host, port))
                t0 = time.perf_counter()
                rt = threading.Thread(target=refresher, daemon=True)
                rt.start()
                client = gen.run()
                rt.join(10.0)
                after = parse_prometheus(scrape_metrics(host, port))
                elapsed = time.perf_counter() - t0

                def delta_m(metric, **labels):
                    return metric_sum(after, metric, **labels) - \
                        metric_sum(before, metric, **labels)

                resp_total = delta_m(
                    "lgbm_tpu_serve_predict_responses_total")
                resp_5xx = sum(
                    delta_m("lgbm_tpu_serve_predict_responses_total",
                            code=c) for c in ("500", "503", "504"))
                per_bucket = _bucket_latency(after, name)
                p99 = max((b["p99_ms"] for b in per_bucket.values()),
                          default=0.0)
                recompiles = registry.get(name).stats.snapshot()[
                    "recompiles"] - r0
                lanes.append({
                    "mode": mode,
                    "config": {"target_qps": qps,
                               "duration_s": duration_s,
                               "base_rounds": base_rounds,
                               "refresh_rounds": refresh_rounds,
                               "shard": int(shard),
                               "bucket_rows": int(bucket_rows),
                               "backend": backend},
                    "qps": round(delta_m(
                        "lgbm_tpu_serve_requests_total",
                        model=name) / elapsed, 2),
                    "availability": round(
                        1.0 - (resp_5xx / resp_total if resp_total
                               else 0.0), 6),
                    "p99_ms": p99,
                    "per_bucket": per_bucket,
                    "recompiles": recompiles,
                    "final_round": registry.round_of(name),
                    "applies": applies,
                    "client": client.summary(),
                })
        finally:
            srv.shutdown()

    by_mode = {l["mode"]: l for l in lanes}
    d = by_mode.get("delta", {})
    delta_ok = (d.get("final_round") == total
                and d.get("recompiles") == 0
                and all(a["status"] == 200 and a["mode"] == "extend"
                        for a in d.get("applies", []))
                and len(d.get("applies", [])) == refresh_rounds)
    avail_ok = all(l["availability"] >= 1.0 for l in lanes)
    swaps_ok = all(a["status"] == 200
                   for a in by_mode.get("full", {}).get("applies", []))
    return {
        "schema": "refresh-under-load-report-v1",
        "git_sha": _git_sha(),
        "backend": backend,
        "verdict": "pass" if (delta_ok and avail_ok and swaps_ok)
                   else "breach",
        "delta_ok": delta_ok,
        "availability_ok": avail_ok,
        "full_swap_ok": swaps_ok,
        "lanes": lanes,
    }


def _zoo_lane(stacking: bool, model_dir: str, names, duration_s: float,
              threads_n: int, rows_per_req: int, features: int,
              zipf_a: float, max_wait_ms: float):
    """One zoo lane: a fresh zoo-mode server over ``model_dir``, every
    tenant cold-loaded on its first touch, then ``duration_s`` of
    zipf-distributed closed-loop traffic.  Returns server-side truth
    (rows/s, device launches, cold-load p99) from /metrics deltas."""
    from lightgbm_tpu.serve.loadgen import (metric_sum, parse_prometheus,
                                            scrape_metrics)
    from lightgbm_tpu.serve.registry import ModelRegistry
    from lightgbm_tpu.serve.server import PredictionServer
    from lightgbm_tpu.serve.zoo import ModelZoo
    from lightgbm_tpu.telemetry.slo import SloEngine

    registry = ModelRegistry()
    zoo = ModelZoo(registry=registry, max_resident=len(names),
                   source_resolver=model_dir, stacking=stacking,
                   batching=True, max_wait_ms=max_wait_ms, warmup=False)
    srv = PredictionServer(registry, port=0, zoo=zoo,
                           slo_engine=SloEngine()).start()
    host, port = srv.host, srv.port
    rng0 = np.random.RandomState(7)
    probe = rng0.randn(rows_per_req, features).tolist()
    try:
        # counters are process-cumulative across lanes: every read below
        # is a delta against this lane's own start
        start = parse_prometheus(scrape_metrics(host, port))
        # first touch of every tenant IS its cold load (counted +
        # timed by zoo_cold_load_ms); also warms the (stack, bucket)
        # programs so the timed window measures steady state
        for name in names:
            code, _ = _post_json(host, port, "/predict",
                                 {"model": name, "rows": probe})
            if code != 200:
                raise RuntimeError(f"prewarm of {name} -> HTTP {code}")
        for name in names:  # second lap: post-stack-formation programs
            _post_json(host, port, "/predict",
                       {"model": name, "rows": probe})

        before = parse_prometheus(scrape_metrics(host, port))
        counts = {"sent": 0, "ok": 0, "errors": {}}
        lock = threading.Lock()
        t0 = time.perf_counter()
        stop_at = t0 + duration_s
        # synchronized burst ticks — the fan-out scoring pattern the
        # stack exists for: every client fires at the same instant, each
        # at its own zipf-sampled tenant, so one arrival wave holds many
        # distinct tenants (per-model serving pays one launch per tenant
        # in the wave; stacked dispatch one launch per wave)
        barrier = threading.Barrier(threads_n)

        def worker(wid):
            rng = np.random.RandomState(100 + wid)
            rows = rng.randn(rows_per_req, features).tolist()
            sent = ok = 0
            errors = {}
            while time.perf_counter() < stop_at:
                try:
                    barrier.wait(timeout=10.0)
                except threading.BrokenBarrierError:
                    break
                i = min(int(rng.zipf(zipf_a)) - 1, len(names) - 1)
                sent += 1
                try:
                    code, _ = _post_json(host, port, "/predict",
                                         {"model": names[i],
                                          "rows": rows})
                except Exception:
                    errors["connect"] = errors.get("connect", 0) + 1
                    continue
                if code == 200:
                    ok += 1
                else:
                    errors[str(code)] = errors.get(str(code), 0) + 1
            barrier.abort()   # release peers parked on the next tick
            with lock:
                counts["sent"] += sent
                counts["ok"] += ok
                for k, v in errors.items():
                    counts["errors"][k] = counts["errors"].get(k, 0) + v

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        after = parse_prometheus(scrape_metrics(host, port))

        def delta(metric, **labels):
            return metric_sum(after, metric, **labels) - \
                metric_sum(before, metric, **labels)

        rows_served = delta("lgbm_tpu_serve_rows_total")
        reqs = delta("lgbm_tpu_serve_requests_total")
        fused = delta("lgbm_tpu_zoo_stack_batches_total")
        # in stacked mode serve_batches_total counts per-LANE slices of
        # a fused launch, so device launches = the fused counter; with
        # stacking off every batch is its own launch
        launches = fused if stacking else delta(
            "lgbm_tpu_serve_batches_total")
        return {
            "mode": "stacked" if stacking else "per-model",
            "rows_per_sec": round(rows_served / elapsed, 1),
            "qps": round(reqs / elapsed, 2),
            "requests": int(reqs),
            "launches": int(launches),
            "launches_per_1k_requests": round(
                1000.0 * launches / reqs, 2) if reqs else 0.0,
            "fused_launches": int(fused),
            "cold_loads": int(
                metric_sum(after, "lgbm_tpu_zoo_cold_loads_total") -
                metric_sum(start, "lgbm_tpu_zoo_cold_loads_total")),
            "cold_load_p99_ms": metric_sum(
                after, "lgbm_tpu_zoo_cold_load_ms_p99"),
            "stack_groups": len(zoo.stack_membership()),
            "availability": round(
                counts["ok"] / counts["sent"], 6) if counts["sent"]
                else 0.0,
            "client": counts,
        }
    finally:
        srv.shutdown()
        zoo.close()


def run_zoo_loadtest(models: int = 16, duration_s: float = 5.0,
                     threads_n: int = 24, rows_per_req: int = 4,
                     features: int = 6, trees: int = 20, leaves: int = 15,
                     zipf_a: float = 1.3, max_wait_ms: float = 10.0):
    """Multi-tenant zoo rung: the SAME zipf workload over ``models``
    same-shape tenants, served stacked (batched cross-model dispatch)
    and per-model; pass needs >= 2x rows/s OR >= 4x fewer launches per
    1k requests for the stacked lane, on top of full availability and
    every tenant cold-loading exactly once."""
    from lightgbm_tpu.utils.backend import default_backend
    from lightgbm_tpu.utils.log import set_verbosity

    backend = default_backend()
    set_verbosity(-1)
    names = [f"tenant{i:02d}" for i in range(int(models))]
    with tempfile.TemporaryDirectory() as tmp:
        model_file = _train_model(trees, leaves, features, tmp)
        zoo_dir = os.path.join(tmp, "zoo")
        os.makedirs(zoo_dir)
        with open(model_file) as fh:
            text = fh.read()
        for name in names:
            with open(os.path.join(zoo_dir, f"{name}.txt"), "w") as fh:
                fh.write(text)
        lanes = [
            _zoo_lane(True, zoo_dir, names, duration_s, threads_n,
                      rows_per_req, features, zipf_a, max_wait_ms),
            _zoo_lane(False, zoo_dir, names, duration_s, threads_n,
                      rows_per_req, features, zipf_a, max_wait_ms),
        ]
    stacked, solo = lanes
    rows_ratio = (stacked["rows_per_sec"] / solo["rows_per_sec"]
                  if solo["rows_per_sec"] else 0.0)
    launch_ratio = (solo["launches_per_1k_requests"] /
                    stacked["launches_per_1k_requests"]
                    if stacked["launches_per_1k_requests"] else 0.0)
    speedup_ok = rows_ratio >= 2.0 or launch_ratio >= 4.0
    avail_ok = all(l["availability"] >= 1.0 for l in lanes)
    cold_ok = all(l["cold_loads"] == len(names) for l in lanes)
    fused_ok = stacked["fused_launches"] > 0 and \
        stacked["stack_groups"] >= 1
    return {
        "schema": "zoo-loadtest-report-v1",
        "git_sha": _git_sha(),
        "backend": backend,
        "verdict": "pass" if (speedup_ok and avail_ok and cold_ok and
                              fused_ok) else "breach",
        "speedup_ok": speedup_ok,
        "availability_ok": avail_ok,
        "cold_loads_ok": cold_ok,
        "fused_ok": fused_ok,
        "rows_ratio": round(rows_ratio, 2),
        "launch_ratio": round(launch_ratio, 2),
        "config": {"models": int(models), "duration_s": duration_s,
                   "threads": int(threads_n),
                   "rows_per_request": int(rows_per_req),
                   "features": int(features), "zipf_a": zipf_a,
                   "max_wait_ms": max_wait_ms, "backend": backend},
        "lanes": lanes,
    }


def run_explain_loadtest(duration_s: float = 5.0, threads_n: int = 4,
                         rows_per_req: int = 8, features: int = 6,
                         trees: int = 20, leaves: int = 15,
                         predict_every: int = 4,
                         p99_threshold_ms: float = 0.0,
                         scrape_interval_s: float = 1.0):
    """Explanation-serving rung: closed-loop ``POST /explain`` traffic
    against a fresh server, with interleaved ``/predict`` requests on
    the same model so the run exercises both lanes at once (the explain
    lane has its own batchers and response counter precisely so a phi
    burst cannot dilute predict availability).  The verdict is read
    back from the server's own telemetry: the explain response counter
    must be 5xx-free, ``serve/explain_latency_p99`` must be met on the
    /slo scrape, the dense compiler must actually have served (ZERO
    fallback batches — a silent walk-path regression flips this), and
    enough requests must land for the SLO window to be falsifiable.
    Client-side additivity (sum(phi) vs served raw scores) rides along
    as context, never as the verdict."""
    from lightgbm_tpu.serve.loadgen import (metric_sum, parse_prometheus,
                                            scrape_json, scrape_metrics)
    from lightgbm_tpu.serve.registry import ModelRegistry
    from lightgbm_tpu.serve.server import PredictionServer
    from lightgbm_tpu.telemetry.slo import SloEngine, set_latency_threshold
    from lightgbm_tpu.utils.backend import default_backend
    from lightgbm_tpu.utils.log import set_verbosity

    backend = default_backend()
    set_verbosity(-1)
    if p99_threshold_ms and p99_threshold_ms > 0:
        set_latency_threshold("serve/explain_latency_p99", p99_threshold_ms)

    model_name = "explain-rung"
    with tempfile.TemporaryDirectory() as tmp:
        model_file = _train_model(trees, leaves, features, tmp)
        registry = ModelRegistry()
        srv = PredictionServer(registry, port=0,
                               slo_engine=SloEngine()).start()
        host, port = srv.host, srv.port
        try:
            registry.load(model_name, model_file, warmup=True)
            rng0 = np.random.RandomState(11)
            probe = rng0.randn(rows_per_req, features).tolist()
            # first /explain pays the lazy dense compile + per-bucket
            # jits; warm it out of the timed window like warmup=True
            # does for the predict lane
            code, warm = _post_json(host, port, "/explain",
                                    {"model": model_name, "rows": probe})
            if code != 200:
                raise RuntimeError(f"explain prewarm -> HTTP {code}")
            # client-side context: served additivity across the HTTP
            # boundary — sum(phi) row-wise vs the raw scores the SAME
            # server serves for the SAME rows
            code, raw = _post_json(host, port, "/predict",
                                   {"model": model_name, "rows": probe,
                                    "raw_score": True})
            phi = np.asarray(warm["contributions"], np.float64)
            additive_ok = bool(
                code == 200 and np.allclose(
                    phi.sum(axis=1),
                    np.asarray(raw["predictions"], np.float64),
                    rtol=1e-4, atol=1e-4))
            # coalesced batches pad to the bucket covering the whole
            # in-flight wave (threads * rows): warm that program too or
            # its jit lands inside the timed window and pollutes p99
            wave_rows = int(threads_n) * int(rows_per_req)
            if wave_rows > rows_per_req:
                _post_json(host, port, "/explain",
                           {"model": model_name,
                            "rows": rng0.randn(
                                wave_rows, features).tolist()})

            before = parse_prometheus(scrape_metrics(host, port))
            counts = {"sent": 0, "ok": 0, "predict_sent": 0,
                      "predict_ok": 0, "errors": {}}
            lock = threading.Lock()
            stop = threading.Event()

            def scraper():
                # burn windows must sample DURING the rung
                while not stop.wait(scrape_interval_s):
                    try:
                        scrape_json(host, port, "/slo")
                    except Exception:
                        pass

            t0 = time.perf_counter()
            stop_at = t0 + duration_s

            def worker(wid):
                rng = np.random.RandomState(200 + wid)
                rows = rng.randn(rows_per_req, features).tolist()
                sent = ok = psent = pok = 0
                errors = {}
                i = 0
                while time.perf_counter() < stop_at:
                    i += 1
                    # every Nth request rides the predict lane: both
                    # lanes stay hot so the isolation claim is tested,
                    # not assumed
                    path = "/predict" if (predict_every and
                                          i % predict_every == 0) \
                        else "/explain"
                    try:
                        code, _ = _post_json(
                            host, port, path,
                            {"model": model_name, "rows": rows})
                    except Exception:
                        errors["connect"] = errors.get("connect", 0) + 1
                        continue
                    if path == "/predict":
                        psent += 1
                        pok += code == 200
                    else:
                        sent += 1
                        ok += code == 200
                    if code != 200:
                        errors[str(code)] = errors.get(str(code), 0) + 1
                with lock:
                    counts["sent"] += sent
                    counts["ok"] += ok
                    counts["predict_sent"] += psent
                    counts["predict_ok"] += pok
                    for k, v in errors.items():
                        counts["errors"][k] = \
                            counts["errors"].get(k, 0) + v

            sc = threading.Thread(target=scraper, daemon=True)
            sc.start()
            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(int(threads_n))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stop.set()
            sc.join(2.0)
            elapsed = time.perf_counter() - t0
            after = parse_prometheus(scrape_metrics(host, port))
            slo_rep = scrape_json(host, port, "/slo")

            def delta(metric, **labels):
                return metric_sum(after, metric, **labels) - \
                    metric_sum(before, metric, **labels)

            explain_reqs = delta("lgbm_tpu_serve_explain_requests_total",
                                 model=model_name)
            resp_total = delta("lgbm_tpu_serve_explain_responses_total")
            resp_5xx = sum(
                delta("lgbm_tpu_serve_explain_responses_total", code=c)
                for c in ("500", "503", "504"))
            fallback_batches = delta(
                "lgbm_tpu_serve_explain_fallback_batches_total")
            fallback_by_reason = {
                lbl.get("reason", "?"): val for lbl, val in
                after.get("lgbm_tpu_serve_explain_fallback", ())
                if val > 0}
            per_bucket = {}
            for lbl, val in after.get(
                    "lgbm_tpu_serve_explain_latency_ms_p99", ()):
                if lbl.get("model") == model_name:
                    per_bucket[lbl.get("bucket", "?")] = {
                        "p99_ms": val,
                        "p50_ms": metric_sum(
                            after, "lgbm_tpu_serve_explain_latency_ms_p50",
                            model=model_name, bucket=lbl.get("bucket"))}
            predict_5xx = sum(
                delta("lgbm_tpu_serve_predict_responses_total", code=c)
                for c in ("500", "503", "504"))
        finally:
            srv.shutdown()

    explain_ent = next(
        (s for s in slo_rep.get("slos", ())
         if s.get("name") == "serve/explain_latency_p99"), {})
    availability = 1.0 - (resp_5xx / resp_total if resp_total else 0.0)
    slo_ok = bool(slo_rep.get("ok"))
    volume_ok = explain_reqs >= 20  # the SLO's min_events window
    dense_ok = fallback_batches == 0
    verdict = "pass" if (slo_ok and availability >= 1.0 and dense_ok and
                         volume_ok and predict_5xx == 0) else "breach"
    return {
        "schema": "explain-loadtest-report-v1",
        "git_sha": _git_sha(),
        "backend": backend,
        "verdict": verdict,
        "verdict_source": "/metrics + /slo scrapes only",
        "slo_ok": slo_ok,
        "availability": round(availability, 6),
        "dense_ok": dense_ok,
        "volume_ok": volume_ok,
        "predict_lane_clean": predict_5xx == 0,
        "explain_qps": round(explain_reqs / elapsed, 2),
        "explain_rows_per_sec": round(
            explain_reqs * rows_per_req / elapsed, 1),
        "fallback_batches": int(fallback_batches),
        "fallback_by_reason": fallback_by_reason,
        "per_bucket": per_bucket,
        "explain_slo": explain_ent,
        "additive_ok": additive_ok,
        "config": {"duration_s": duration_s, "threads": int(threads_n),
                   "rows_per_request": int(rows_per_req),
                   "features": int(features), "trees": int(trees),
                   "leaves": int(leaves),
                   "predict_every": int(predict_every),
                   "backend": backend},
        "slo": slo_rep,
        "client": counts,
    }


def explain_to_bench_matrix(report) -> dict:
    """bench-matrix-v1 rows for the nightly gate: one explain qps row,
    one p99 row per bucket, one fallback row (any drift off 0 means the
    dense compiler stopped serving and the host walk absorbed the load
    — a perf cliff the latency rows alone could survive), and the
    verdict."""
    rows = [{"name": "explain_loadtest",
             "config": report["config"],
             "qps": report["explain_qps"],
             "rows_per_sec": report["explain_rows_per_sec"],
             "availability": report["availability"],
             "interpreted": False}]
    for b, lat in sorted(report["per_bucket"].items()):
        rows.append({"name": f"explain_loadtest_p99_b{b}",
                     "config": {"bucket": b, **report["config"]},
                     "p99_ms": lat["p99_ms"],
                     "interpreted": False})
    rows.append({"name": "explain_fallbacks",
                 "config": report["config"],
                 "fallback_batches": report["fallback_batches"],
                 "interpreted": False})
    rows.append({"name": "explain_verdict",
                 "slo_ok": bool(report["slo_ok"]),
                 "verdict": report["verdict"]})
    return {
        "schema": "bench-matrix-v1",
        "bench": "explain-loadtest",
        "git_sha": report["git_sha"],
        "backend": report["backend"],
        "rows": rows,
    }


def zoo_to_bench_matrix(report) -> dict:
    """bench-matrix-v1 rows for the nightly gate: per lane one rows/s
    row and one launches-per-1k row (the stacked lane drifting toward
    the per-model launch count is a regression of the fused dispatch),
    one cold-load p99 row, and the verdict."""
    rows = []
    for lane in report["lanes"]:
        rows.append({"name": f"zoo_{lane['mode']}",
                     "config": report["config"],
                     "rows_per_sec": lane["rows_per_sec"],
                     "availability": lane["availability"],
                     "interpreted": False})
        rows.append({"name": f"zoo_{lane['mode']}_launches",
                     "config": report["config"],
                     "launches_per_1k": lane["launches_per_1k_requests"],
                     "interpreted": False})
    rows.append({"name": "zoo_cold_load",
                 "config": report["config"],
                 "p99_ms": report["lanes"][0]["cold_load_p99_ms"],
                 "interpreted": False})
    rows.append({"name": "zoo_verdict",
                 "slo_ok": report["verdict"] == "pass",
                 "verdict": report["verdict"]})
    return {
        "schema": "bench-matrix-v1",
        "bench": "zoo-loadtest",
        "git_sha": report["git_sha"],
        "backend": report["backend"],
        "rows": rows,
    }


def refresh_to_bench_matrix(report) -> dict:
    """bench-matrix-v1 rows for the nightly gate: per refresh lane one
    p99 row and one recompile row (delta lane drifting off 0 recompiles
    is a regression of the in-envelope splice), plus the verdict."""
    rows = []
    for lane in report["lanes"]:
        rows.append({"name": f"refresh_{lane['mode']}_p99",
                     "config": lane["config"],
                     "p99_ms": lane["p99_ms"],
                     "availability": lane["availability"],
                     "interpreted": False})
        rows.append({"name": f"refresh_{lane['mode']}_recompiles",
                     "config": lane["config"],
                     "recompiles": lane["recompiles"],
                     "interpreted": False})
    rows.append({"name": "refresh_verdict",
                 "slo_ok": report["verdict"] == "pass",
                 "verdict": report["verdict"]})
    return {
        "schema": "bench-matrix-v1",
        "bench": "refresh-under-load",
        "git_sha": report["git_sha"],
        "backend": report["backend"],
        "rows": rows,
    }


def fleet_chaos_to_bench_matrix(report) -> dict:
    """bench-matrix-v1 rows for the nightly regression gate: one qps
    row (throughput direction) and one SLO verdict row (a recovery that
    stops meeting the availability SLO flips met -> breached and fails
    the gate)."""
    return {
        "schema": "bench-matrix-v1",
        "bench": "fleet-chaos",
        "git_sha": report["git_sha"],
        "backend": report["backend"],
        "rows": [
            {"name": "fleet_chaos", "config": report["config"],
             "qps": report["qps"],
             "availability": report["availability"],
             "interpreted": False},
            {"name": "fleet_chaos_slo",
             "slo_ok": bool(report["slo_ok"] and report["recovered"]
                            and report["crashed"]),
             "verdict": report["verdict"]},
        ],
    }


def to_bench_matrix(report) -> dict:
    """bench-matrix-v1 record for the nightly regression gate: per rung
    one rows/s row and one qps row (each metric on its own row — the
    gate compares one key per row, so sharing a row would leave qps
    unjudged), one latency row per (rung, bucket), one SLO verdict
    row."""
    rows = []
    for r in report["rungs"]:
        rows.append({"name": f"loadtest_{r['label']}",
                     "config": r["config"],
                     "rows_per_sec": r["rows_per_sec"],
                     "availability": r["availability"],
                     "interpreted": False})
        rows.append({"name": f"loadtest_{r['label']}_qps",
                     "config": r["config"],
                     "qps": r["qps"],
                     "interpreted": False})
        for b, lat in sorted(r["per_bucket"].items()):
            rows.append({"name": f"loadtest_{r['label']}_p99_b{b}",
                         "config": {"bucket": b, **r["config"]},
                         "p99_ms": lat["p99_ms"],
                         "queue_wait_p50_ms": lat["queue_wait_p50_ms"],
                         "device_p50_ms": lat["device_p50_ms"],
                         "interpreted": False})
    rows.append({"name": "loadtest_slo",
                 "slo_ok": bool(report["slo_ok"]),
                 "verdict": report["verdict"]})
    return {
        "schema": "bench-matrix-v1",
        "bench": "loadtest",
        "git_sha": report["git_sha"],
        "backend": report["backend"],
        "rows": rows,
    }


def main(argv) -> int:
    from lightgbm_tpu.utils.cache import configure_compile_cache
    configure_compile_cache()
    json_path = slo_path = ""
    if "--json" in argv:
        json_path = argv[argv.index("--json") + 1]
    if "--slo-report" in argv:
        slo_path = argv[argv.index("--slo-report") + 1]

    if "--fleet-chaos" in argv:
        report = run_fleet_chaos(
            workers=int(os.environ.get("FLEET_WORKERS", 2)),
            duration_s=float(os.environ.get("FLEET_DURATION", 8.0)),
            qps=float(os.environ.get("FLEET_QPS", 30.0)),
            crash_after=int(os.environ.get("FLEET_CRASH_AFTER", 40)),
            recovery_window_s=float(
                os.environ.get("FLEET_RECOVERY_S", 10.0)))
        print(json.dumps({
            "verdict": report["verdict"],
            "crashed": report["crashed"],
            "recovered": report["recovered"],
            "slo_ok": report["slo_ok"],
            "all_requests_terminal": report["all_requests_terminal"],
            "availability": report["availability"],
            "fleet_restarts_total": report["fleet_restarts_total"],
            "fleet_retries_total": report["fleet_retries_total"]},
            indent=2), flush=True)
        if slo_path:
            with open(slo_path, "w") as fh:
                json.dump(report, fh, indent=2, default=str)
        if json_path:
            with open(json_path, "w") as fh:
                json.dump(fleet_chaos_to_bench_matrix(report), fh,
                          indent=2, default=str)
        return 0 if report["verdict"] == "pass" else 1

    if "--zoo" in argv:
        report = run_zoo_loadtest(
            models=int(os.environ.get("ZOO_MODELS", 16)),
            duration_s=float(os.environ.get("ZOO_DURATION", 5.0)),
            threads_n=int(os.environ.get("ZOO_THREADS", 24)),
            rows_per_req=int(os.environ.get("ZOO_ROWS", 4)),
            zipf_a=float(os.environ.get("ZOO_ZIPF", 1.3)),
            max_wait_ms=float(os.environ.get("ZOO_MAX_WAIT_MS", 10.0)))
        print(json.dumps({
            "verdict": report["verdict"],
            "speedup_ok": report["speedup_ok"],
            "availability_ok": report["availability_ok"],
            "cold_loads_ok": report["cold_loads_ok"],
            "rows_ratio": report["rows_ratio"],
            "launch_ratio": report["launch_ratio"],
            "lanes": [{k: l[k] for k in
                       ("mode", "rows_per_sec", "qps",
                        "launches_per_1k_requests", "cold_loads",
                        "cold_load_p99_ms", "availability")}
                      for l in report["lanes"]]}, indent=2), flush=True)
        if slo_path:
            with open(slo_path, "w") as fh:
                json.dump(report, fh, indent=2, default=str)
        if json_path:
            with open(json_path, "w") as fh:
                json.dump(zoo_to_bench_matrix(report), fh,
                          indent=2, default=str)
        return 0 if report["verdict"] == "pass" else 1

    if "--refresh" in argv:
        report = run_refresh_under_load(
            duration_s=float(os.environ.get("REFRESH_DURATION", 6.0)),
            qps=float(os.environ.get("REFRESH_QPS", 40.0)),
            base_rounds=int(os.environ.get("REFRESH_BASE_ROUNDS", 4)),
            refresh_rounds=int(os.environ.get("REFRESH_ROUNDS", 4)),
            shard=int(os.environ.get("REFRESH_SHARD", 16)))
        print(json.dumps({
            "verdict": report["verdict"],
            "delta_ok": report["delta_ok"],
            "availability_ok": report["availability_ok"],
            "full_swap_ok": report["full_swap_ok"],
            "lanes": [{k: l[k] for k in
                       ("mode", "p99_ms", "recompiles", "availability",
                        "final_round")} for l in report["lanes"]]},
            indent=2), flush=True)
        if slo_path:
            with open(slo_path, "w") as fh:
                json.dump(report, fh, indent=2, default=str)
        if json_path:
            with open(json_path, "w") as fh:
                json.dump(refresh_to_bench_matrix(report), fh,
                          indent=2, default=str)
        return 0 if report["verdict"] == "pass" else 1

    if "--explain" in argv:
        report = run_explain_loadtest(
            duration_s=float(os.environ.get("EXPLAIN_DURATION", 5.0)),
            threads_n=int(os.environ.get("EXPLAIN_THREADS", 4)),
            rows_per_req=int(os.environ.get("EXPLAIN_ROWS", 8)),
            features=int(os.environ.get("EXPLAIN_FEATURES", 6)),
            trees=int(os.environ.get("EXPLAIN_TREES", 20)),
            leaves=int(os.environ.get("EXPLAIN_LEAVES", 15)),
            predict_every=int(os.environ.get("EXPLAIN_PREDICT_EVERY", 4)),
            p99_threshold_ms=float(os.environ.get("EXPLAIN_P99_MS", 0.0)))
        print(json.dumps({
            "verdict": report["verdict"],
            "slo_ok": report["slo_ok"],
            "availability": report["availability"],
            "dense_ok": report["dense_ok"],
            "volume_ok": report["volume_ok"],
            "predict_lane_clean": report["predict_lane_clean"],
            "additive_ok": report["additive_ok"],
            "explain_qps": report["explain_qps"],
            "explain_rows_per_sec": report["explain_rows_per_sec"],
            "fallback_batches": report["fallback_batches"],
            "per_bucket": report["per_bucket"]}, indent=2), flush=True)
        if slo_path:
            with open(slo_path, "w") as fh:
                json.dump(report, fh, indent=2, default=str)
        if json_path:
            with open(json_path, "w") as fh:
                json.dump(explain_to_bench_matrix(report), fh,
                          indent=2, default=str)
        return 0 if report["verdict"] == "pass" else 1

    ladder = [tok.strip() for tok in
              os.environ.get("LOAD_LADDER", "closed").split(",")
              if tok.strip()]
    report = run_loadtest(
        ladder=ladder,
        duration_s=float(os.environ.get("LOAD_DURATION", 5.0)),
        workers=int(os.environ.get("LOAD_WORKERS", 3)),
        features=int(os.environ.get("LOAD_FEATURES", 4)),
        trees=int(os.environ.get("LOAD_TREES", 20)),
        leaves=int(os.environ.get("LOAD_LEAVES", 15)),
        bucket_mix=_parse_bucket_mix(
            os.environ.get("LOAD_BUCKETS", "4096")),
        arrival=os.environ.get("LOAD_ARRIVAL", "uniform"),
        target_rows_per_s=float(os.environ.get("LOAD_TARGET_ROWS_S", 1e5)),
        p99_threshold_ms=float(os.environ.get("LOAD_P99_MS", 0.0)),
        max_queue_rows=int(os.environ.get("LOAD_MAX_QUEUE_ROWS", 0)))

    for r in report["rungs"]:
        print(json.dumps({
            "rung": r["label"], "rows_per_sec": r["rows_per_sec"],
            "qps": r["qps"], "availability": r["availability"],
            "slo_ok": r["slo"].get("ok")}), flush=True)
    print(json.dumps({
        "verdict": report["verdict"], "slo_ok": report["slo_ok"],
        "rows_ok": report["rows_ok"],
        "peak_rows_per_sec": report["peak_rows_per_sec"],
        "target_rows_per_s": report["target_rows_per_s"]}), flush=True)

    if slo_path:
        with open(slo_path, "w") as fh:
            json.dump(report, fh, indent=2, default=str)
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(to_bench_matrix(report), fh, indent=2, default=str)
    return 0 if report["verdict"] == "pass" else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
