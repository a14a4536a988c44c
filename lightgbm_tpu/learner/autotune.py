"""One-shot histogram-implementation autotune with a persistent cache.

The reference times its col-wise vs row-wise histogram construction on
the first iteration and keeps the winner (reference: src/io/dataset.cpp
:659-670 ``ShareStates`` force_col_wise/force_row_wise timing).  The TPU
analog choice spans the kernel-v2 variant matrix: the Pallas MXU kernel
(DMA-pipelined or BlockSpec-fetched, 4-bit-packed bins when max_bin
fits a nibble) vs the XLA onehot formulation — and on CPU hosts the
scatter-add ``segment`` path vs the joint-nibble ``packed4`` scatter.
The static table in ``resolve_hist_impl`` is right for benchmark-scale
shapes, but small or oddly-shaped datasets (tiny N, very wide F, tiny
max_bin) can go either way — so when the binned matrix is small enough
that a few extra compiles are cheap, time the candidates on the REAL
data once and keep the winner per (N, F, B) shape.

Measured winners persist to a per-(shape, device kind) ON-DISK cache
next to the compile cache (``hist_autotune.json`` under
``utils/cache.cache_root()``; ``LGBM_TPU_AUTOTUNE_CACHE`` names another
file, "" disables persistence), so repeated processes — test suites,
cron retrains, sweep workers — skip the re-measurement pass entirely.
A timing is a fact about one chip generation, so the key carries
``device_kind``, not just "tpu".

Candidate grammar: an impl name (``segment`` / ``onehot`` / ``packed4``
/ ``pallas``), optionally suffixed for the pallas kernel variants —
``pallas:blockspec`` (v1 implicit pipeline), ``pallas:packed4``
(DMA + nibble-packed bins).  ``pallas`` alone is the DMA pipeline.
The caller maps a suffixed winner back onto config knobs
(models/gbdt.py: ``tpu_histogram_impl`` + ``tpu_pallas_pipeline``).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Tuple

import numpy as np

# shape -> winning impl, process-lifetime cache
_CACHE: Dict[Tuple[str, int, int, int, tuple], str] = {}
_DISK_LOADED: Dict[str, Dict[str, str]] = {}

# above this many binned cells the static choice (pallas on TPU) is
# reliably right and the probe's compile time isn't worth it
AUTOTUNE_MAX_CELLS = 1 << 22


def _cache_path() -> Optional[str]:
    p = os.environ.get("LGBM_TPU_AUTOTUNE_CACHE")
    if p == "":
        return None
    if p:
        return p
    from ..utils.cache import cache_root
    return os.path.join(cache_root(), "hist_autotune.json")


def _disk_load(path: str) -> Dict[str, str]:
    if path in _DISK_LOADED:
        return _DISK_LOADED[path]
    data: Dict[str, str] = {}
    try:
        with open(path) as fh:
            raw = json.load(fh)
        if isinstance(raw, dict) and raw.get("schema") == "hist-autotune-v1":
            data = {str(k): str(v) for k, v in raw.get("winners", {}).items()}
    except Exception:
        data = {}
    _DISK_LOADED[path] = data
    return data


def _disk_store(path: str, key: str, win: str) -> None:
    # merge from a FRESH read, not the memo: concurrent sweep workers
    # append entries between our reads, and a stale-memo merge would
    # silently clobber their persisted winners
    _DISK_LOADED.pop(path, None)
    data = dict(_disk_load(path))
    data[key] = win
    payload = json.dumps({"schema": "hist-autotune-v1", "winners": data},
                         indent=0, sort_keys=True).encode()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        from ..io_utils import atomic_write_bytes
        atomic_write_bytes(path, payload)
        _DISK_LOADED[path] = data
    except Exception:
        pass  # persistence is best-effort; the in-process cache still holds


def _disk_key(device_kind: str, n: int, f: int, b: int, candidates) -> str:
    return f"{device_kind}/{n}x{f}x{b}/" + ",".join(candidates)


def default_candidates(backend: str, max_bins: int) -> tuple:
    """The variant set worth probing on this backend/shape."""
    if backend == "tpu":
        cands = ["pallas", "pallas:blockspec", "onehot"]
        if max_bins <= 16:
            cands.insert(1, "pallas:packed4")
        return tuple(cands)
    if max_bins <= 16:
        return ("segment", "packed4")
    return ("segment",)


def _make_runner(impl: str, X_binned: np.ndarray, max_bins: int):
    """Build a zero-arg measured build closure for one candidate."""
    import jax.numpy as jnp
    n, f = X_binned.shape
    rng = np.random.RandomState(0)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(np.abs(rng.randn(n)).astype(np.float32))
    mask = jnp.ones((n,), jnp.float32)
    base, _, variant = impl.partition(":")
    if base == "pallas":
        from ..ops.histogram_pallas import build_histogram_pallas, pad_rows
        from .serial import feature_major_bins
        n_pad = pad_rows(n)
        packed = variant == "packed4"
        bins_t = feature_major_bins(jnp.asarray(X_binned), n_pad, packed)
        pipeline = "blockspec" if variant == "blockspec" else "dma"
        gp = jnp.pad(grad, (0, n_pad - n))
        hp = jnp.pad(hess, (0, n_pad - n))
        mp = jnp.pad(mask, (0, n_pad - n))

        def run():
            return build_histogram_pallas(bins_t, gp, hp, mp,
                                          num_bins=int(max_bins),
                                          pipeline=pipeline,
                                          bins_packed=packed)
    else:
        from ..ops.histogram import build_histogram
        bins_d = jnp.asarray(X_binned)

        def run(impl=base):
            return build_histogram(bins_d, grad, hess, mask,
                                   num_bins=int(max_bins), impl=impl)
    return run


def pick_hist_impl(X_binned: np.ndarray, max_bins: int,
                   candidates=None, reps: int = 10) -> str:
    """Time one full histogram build per candidate variant on the actual
    data shapes; return the faster (ties -> first candidate).

    Measurement is amortized over ``reps`` builds with a single host
    sync, so the sync is a CONSTANT bias shared by both candidates, not
    part of the per-build signal.  A candidate that fails to compile or
    run raises: a kernel Mosaic refuses must surface, not lose quietly
    to ``onehot``.  The static default (candidates[0]) gets a
    1.3x hysteresis margin: a wrong flip away from the measured-good
    default costs 5-10x per histogram pass at wave-grower shapes, so the
    probe must beat real noise, not tie with it."""
    import jax
    import jax.numpy as jnp
    n, f = X_binned.shape
    if candidates is None:
        from ..utils.backend import default_backend
        candidates = default_candidates(default_backend(), int(max_bins))
    candidates = tuple(candidates)
    if len(candidates) == 1:
        return candidates[0]
    kind = jax.devices()[0].device_kind
    key = (kind, n, f, int(max_bins), candidates)
    hit = _CACHE.get(key)
    if hit in candidates:
        return hit
    path = _cache_path()
    dkey = _disk_key(kind, n, f, int(max_bins), candidates)
    if path:
        disk_hit = _disk_load(path).get(dkey)
        if disk_hit in candidates:
            _CACHE[key] = disk_hit
            from ..utils.log import log_info
            log_info(f"histogram autotune at shape ({n}, {f}, {max_bins}): "
                     f"{disk_hit} (cached winner, {path})")
            return disk_hit

    times = {}
    for impl in candidates:
        run = _make_runner(impl, X_binned, max_bins)
        out = run()                       # compile + warm
        _ = float(jnp.ravel(out)[0])
        t0 = time.perf_counter()
        for _i in range(reps):
            out = run()
        _ = float(jnp.ravel(out)[0])
        times[impl] = (time.perf_counter() - t0) / reps
    win = min(candidates, key=lambda i: times[i])
    if win != candidates[0] and \
            times[win] > times[candidates[0]] / 1.3:
        win = candidates[0]
    from ..utils.log import log_info
    log_info("histogram autotune at shape "
             f"({n}, {f}, {max_bins}): " +
             ", ".join(f"{k}={v * 1e3:.2f}ms" for k, v in times.items()) +
             f" -> {win}")
    _CACHE[key] = win
    if path:
        _disk_store(path, dkey, win)
    return win


def apply_winner(cfg, win: str) -> None:
    """Map a (possibly suffixed) winning variant onto config knobs.

    ALL three knobs are pinned, not just the suffixed one: a plain
    "pallas" winner beat the packed/blockspec candidates, so the
    default-on pack4 must be switched OFF for training to run the
    variant that actually won the measurement."""
    base, _, variant = win.partition(":")
    cfg.tpu_histogram_impl = base
    if base == "pallas":
        cfg.tpu_hist_pack4 = variant == "packed4"
        cfg.tpu_pallas_pipeline = ("blockspec" if variant == "blockspec"
                                   else "dma")
