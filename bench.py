"""Benchmark: boosting iterations/sec on Higgs-shaped data.

Reproduces the reference's headline config (docs/Experiments.rst:110 —
Higgs 10.5M x 28, 500 trees, 255 leaves, 255 bins, lr 0.1; reference CPU:
130.094 s => 3.84 iters/s on 2x E5-2690v4; see BASELINE.md) on synthetic
Higgs-like data, on one TPU chip, in ONE process (a chip belongs to one
process at a time; this script never spawns a child).  Off-TPU it fails:
a number from a CPU run is never printed under this metric's name.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Measurement: 2 warmup updates (compile + cache), then BENCH_WINDOWS
timed windows of trees with ONE device-forcing scalar sync each; the
headline value is the MEDIAN window rate.  The JSON also carries
per-window rates, the device as JAX reports it, and the on-chip kernel
exactness check shared with chip_smoke.py (int8 path must be exactly 0).

Env knobs: BENCH_ROWS (default 10_500_000 — the BASELINE's true scale),
BENCH_TREES (default 50), BENCH_WINDOWS (5), BENCH_LEAVES (255),
BENCH_BINS (255), BENCH_QUANT (default 1: int8 quantized-gradient
histograms at 254 levels with stochastic rounding + exact leaf renewal —
the TPU configuration of the reference's own use_quantized_grad feature;
set 0 for exact bf16 hi/lo histograms), BENCH_SELFCHECK (default 1).
"""

import json
import os
import statistics
import time

BASELINE_ITERS_PER_SEC = 500.0 / 130.094  # reference Higgs CPU number


def main() -> None:
    import jax

    from lightgbm_tpu.utils.backend import default_backend
    from lightgbm_tpu.utils.cache import configure_compile_cache

    backend = default_backend()     # raises when the chip cannot come up
    if backend != "tpu":
        raise SystemExit(f"bench.py measures the chip; JAX reports "
                         f"platform={backend!r}. Not running.")
    configure_compile_cache()
    dev = jax.devices()[0]
    _run({"platform": dev.platform, "kind": dev.device_kind,
          "count": len(jax.devices())})


def _run(device: dict) -> None:
    rows = int(os.environ.get("BENCH_ROWS", 10_500_000))
    trees = int(os.environ.get("BENCH_TREES", 50))
    leaves = int(os.environ.get("BENCH_LEAVES", 255))
    windows = max(1, int(os.environ.get("BENCH_WINDOWS", 5)))
    bins = int(os.environ.get("BENCH_BINS", 255))

    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.log import set_verbosity

    from chip_smoke import FEATURES as f
    from chip_smoke import kernel_selfcheck, make_data

    set_verbosity(-1)
    X, y, _, _ = make_data(rows, 0)

    params = {
        "objective": "binary", "num_leaves": leaves, "max_bin": bins,
        "learning_rate": 0.1, "min_data_in_leaf": 20, "verbosity": -1,
    }
    quant = int(os.environ.get("BENCH_QUANT", 1))
    if quant:
        params.update({"use_quantized_grad": True,
                       "num_grad_quant_bins": 254,
                       "quant_train_renew_leaf": True})
    ds = lgb.Dataset(X, y, params=params)
    booster = lgb.Booster(params=params, train_set=ds)

    def sync():
        # ONE scalar host copy forces every queued device computation
        return float(jnp.sum(booster._gbdt.score))

    # warmup: compile + first trees (the second update also exercises the
    # donation/steady path once before any timed window)
    booster.update()
    booster.update()
    sync()

    per_window = max(1, trees // windows)
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(per_window):
            booster.update()
        sync()
        rates.append(per_window / (time.perf_counter() - t0))
    iters_per_sec = statistics.median(rates)

    extra = {}
    if int(os.environ.get("BENCH_SELFCHECK", 1)):
        extra = {f"kernel_{k}": v for k, v in kernel_selfcheck().items()}
    print(json.dumps({
        "metric": f"boosting_iters_per_sec (binary, {rows}x{f}, "
                  f"{leaves} leaves, {bins} bins"
                  f"{', quantized-grad int8' if quant else ''}, "
                  f"{device['kind']})",
        "value": round(iters_per_sec, 4),
        "unit": "iters/s",
        "vs_baseline": round(iters_per_sec / BASELINE_ITERS_PER_SEC, 4),
        "window_rates": [round(r, 4) for r in rates],
        "device": device,
        **extra,
    }))


if __name__ == "__main__":
    main()
