#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the training main path starts on
the chip.  One process, no children, no network.

What it runs, through the entry points a user calls:

  kernels  every Pallas entry the default TPU routing reaches, against the
           XLA one-hot formulation (int8 sums must differ by exactly 0)
  q8       ``lgb.train`` on synthetic Higgs-shaped data at the flagship
           width (10.5M x 28 f32, binary, 255 leaves, 255 bins, int8
           quantized gradients), a few boosting steps past the compile,
           ``predict`` on 131072 held-out rows (the dense MXU predictor),
           ``model_to_string`` -> ``Booster(model_str=...)`` round trip
  exact    the same few steps with ``use_quantized_grad=false`` (bf16 hi/lo)
  data     (more than one chip visible) the q8 configuration under
           ``tree_learner=data`` across all chips: parity with the serial
           learner, then the flagship defaults with sharding asserted.
           A pass/fail proof only: the MEASURED four-chip path is the
           benchmark cell, ``chiprun --chips 4 -- python3 -m chipbench.run
           --workload criteo-q8-dp4.train --seed N --seconds 51 --trace 0|1``

It asserts what actually ran (grower, kernels, interpret mode, sharding),
not what a warning said.  Any failed phase ends the run non-zero; off-TPU
the run fails before training.  The last stdout line is one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
No time, rate or utilization printed here is a benchmark result: compile
and wall seconds are reported as set-up time only.
"""

from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

ROWS = 10_500_000
FEATURES = 28
HOLDOUT = 131_072    # one predict bucket; compile time grows with it
STEPS = 6            # the first step compiles; >=5 run past it
KERNEL_ROWS = 1 << 18
DP_STEPS = 3
DP_TOL = 2e-5        # serial vs data-parallel, as __graft_entry__.py
PREDICT_CHUNK = 16_384   # rows per predict call (see predict_held_out)
AUC_FLOOR = 0.75     # held-out, a few 255-leaf trees; chance is 0.5

BASE_PARAMS = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
               "learning_rate": 0.1, "min_data_in_leaf": 20,
               "verbosity": -1}
Q8_PARAMS = {"use_quantized_grad": True, "num_grad_quant_bins": 254,
             "quant_train_renew_leaf": True}

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:6.1f}s] {msg}",
          flush=True)


def check(cond: bool, what: str) -> None:
    """An assertion that survives ``python -O``."""
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


# -- device ------------------------------------------------------------------

def find_device() -> dict:
    """The device as JAX reports it, plus the installed versions."""
    from importlib import metadata

    import jax
    import jaxlib
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    log(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    return dev


# -- data --------------------------------------------------------------------

def make_data(rows: int, holdout: int, features: int = FEATURES,
              seed: int = 0):
    """Higgs-like dense floats with learnable structure (bench.py's
    generator), split into train and held-out rows."""
    rng = np.random.RandomState(seed)
    n = rows + holdout
    X = rng.randn(n, features).astype(np.float32)
    w = rng.randn(features) / np.sqrt(features)
    logit = X @ w + 0.3 * np.sin(2 * X[:, 0]) * X[:, 1]
    y = (logit + rng.randn(n) * 0.5 > 0).astype(np.float64)
    return X[:rows], y[:rows], X[rows:], y[rows:]


# -- kernel exactness --------------------------------------------------------

def _row_update_reference(cols, rl, tab):
    """Plain-XLA form of ops/histogram_pallas.wave_row_update_pallas: the
    W splits applied one after the other."""
    import jax.numpy as jnp
    rl = rl.astype(jnp.int32)
    ch = jnp.full_like(rl, -1)
    for j in range(cols.shape[0]):
        col = cols[j].astype(jnp.int32)
        thr, nanb, dlft, small, sel, newid, act = (tab[i, j]
                                                   for i in range(7))
        go_left = jnp.where(col == nanb, dlft, (col <= thr).astype(jnp.int32))
        upd = (rl == sel) & (act > 0)
        ch = jnp.where(upd & (go_left == small), j, ch)
        rl = jnp.where(upd & (go_left == 0), newid, rl)
    return rl, ch.astype(jnp.int8)


def kernel_selfcheck(rows: int = KERNEL_ROWS, features: int = FEATURES,
                     interpret=None, pipeline=None) -> dict:
    """Every Pallas entry the default TPU routing reaches vs its XLA
    reference, at the flagship tile shape (B=256) and in the nibble-packed
    form (B=16, ``tpu_hist_pack4``).  Returns the measured differences;
    raises when an integer path is not exact or a bf16 hi/lo path leaves
    its 1e-5 relative budget."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram import (build_histogram,
                                            build_histogram_leaves)
    from lightgbm_tpu.ops.histogram_pallas import (
        LEAF_CHANNELS, Q_LEAF_CHANNELS, build_histogram_pallas,
        build_histogram_pallas_leaves, build_histogram_pallas_leaves_q8,
        bin_rows_view, pack_bins4, pack_weights8, pad_rows,
        wave_row_update_pallas, wave_trial_channels_pallas)

    n = pad_rows(rows)
    kw = dict(interpret=interpret, pipeline=pipeline)
    rng = np.random.RandomState(0)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(rng.rand(n).astype(np.float32))
    mask = jnp.asarray((rng.rand(n) > 0.2).astype(np.float32))
    w8 = pack_weights8(grad, hess, mask)
    ch_b = jnp.asarray(rng.randint(-1, LEAF_CHANNELS, n).astype(np.int8))
    ch_q = jnp.asarray(rng.randint(-1, Q_LEAF_CHANNELS, n).astype(np.int8))
    wch = jnp.asarray(np.concatenate([
        rng.randint(-127, 128, (1, n)), rng.randint(0, 128, (1, n)),
        np.ones((1, n)), np.zeros((5, n))]).astype(np.int8))
    out = {}

    def rel(got, ref):
        return float(jnp.max(jnp.abs(got - ref)) /
                     jnp.maximum(1.0, jnp.max(jnp.abs(ref))))

    for B, packed in ((256, False), (16, True)):
        tag = "b16_packed4" if packed else "b256"
        bins = rng.randint(0, B, (n, features)).astype(np.uint8)
        rows_major = jnp.asarray(bins)
        bins_t = jnp.asarray(np.ascontiguousarray(bins.T))
        src = pack_bins4(bins_t) if packed else bins_t
        pk = dict(kw, bins_packed=packed)

        # single-leaf kernel: the root pass ...
        ref = build_histogram(rows_major, grad, hess, mask, num_bins=B,
                              impl="onehot")
        got = build_histogram_pallas(src, grad, hess, mask, num_bins=B, **pk)
        out[f"single_{tag}_rel"] = rel(got, ref)
        # bf16 hi/lo 25-leaf kernel
        ref = build_histogram_leaves(
            rows_major, grad * mask, hess * mask, (mask > 0) * 1.0, ch_b,
            num_channels=LEAF_CHANNELS, num_bins=B, impl="onehot")
        got = build_histogram_pallas_leaves(src, w8, ch_b, num_bins=B, **pk)
        out[f"leaves_{tag}_rel"] = rel(got, ref)
        # int8 42-leaf kernel: integer sums, the difference must be 0
        ref = build_histogram_leaves(
            rows_major, wch[0].astype(jnp.float32),
            wch[1].astype(jnp.float32), jnp.ones((n,), jnp.float32), ch_q,
            num_channels=Q_LEAF_CHANNELS, num_bins=B, impl="onehot")
        got = build_histogram_pallas_leaves_q8(src, wch, ch_q, num_bins=B,
                                               **pk)
        out[f"leaves_q8_{tag}_abs"] = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - jnp.round(ref))))

    # ... and the quantized leaf-refit pass: row_leaf as a one-feature bin
    # column with kr=4096 (learner/wave.py renew_leaf)
    leaf = rng.randint(0, 255, n)
    ref = build_histogram(jnp.asarray(leaf.astype(np.uint8)[:, None]), grad,
                          hess, mask, num_bins=256, impl="onehot")
    got = build_histogram_pallas(
        jnp.asarray(leaf.astype(np.uint8)[None, :]), grad, hess, mask,
        num_bins=256, kr=4096, **kw)
    out["single_refit_rel"] = rel(got, ref)

    # wave row update + trial channels: integer outputs, exact
    W = Q_LEAF_CHANNELS
    cols = jnp.asarray(rng.randint(0, 256, (W, n)).astype(np.uint8))
    rl = jnp.asarray(leaf.astype(np.int32))
    split_leaf = rng.permutation(255)[:W].astype(np.int32)
    tab = jnp.asarray(np.stack([
        rng.randint(0, 255, W), np.where(rng.rand(W) < 0.5, 255, -1),
        rng.randint(0, 2, W), rng.randint(0, 2, W), split_leaf,
        255 + np.arange(W), (rng.rand(W) < 0.9).astype(np.int64),
        np.zeros(W)]).astype(np.int32))
    rl_ref, ch_ref = _row_update_reference(cols, rl, tab)
    rl_got, ch_got = wave_row_update_pallas(cols, rl, tab, **kw)
    out["row_update_mismatches"] = int(jnp.sum(rl_got != rl_ref) +
                                       jnp.sum(ch_got != ch_ref))
    # ... and as the grower calls it: the kernel handed a bin matrix (the
    # columns, shuffled) and the W feature ids, fetching its own columns
    order = rng.permutation(W)
    fetch = dict(kw, feats=jnp.asarray(np.argsort(order).astype(np.int32)))
    bins = bin_rows_view(cols[order], pipeline)
    rl_got, ch_got = wave_row_update_pallas(bins, rl, tab, **fetch)
    out["row_update_fetch_mismatches"] = int(jnp.sum(rl_got != rl_ref) +
                                             jnp.sum(ch_got != ch_ref))
    trial_tab = tab.at[5].set(tab[4])        # new_right_id = split leaf
    _, ch_ref = _row_update_reference(cols, rl, trial_tab)
    ch_got = wave_trial_channels_pallas(
        cols, rl, tab[4], tab[0], tab[1], tab[2], tab[3], tab[6], **kw)
    out["trial_channels_mismatches"] = int(jnp.sum(ch_got != ch_ref))
    ch_got = wave_trial_channels_pallas(
        bins, rl, tab[4], tab[0], tab[1], tab[2], tab[3], tab[6], **fetch)
    out["trial_channels_fetch_mismatches"] = int(jnp.sum(ch_got != ch_ref))

    for name, v in out.items():
        exact = not name.endswith("_rel")
        check(v == 0 if exact else v < 1e-5,
              f"kernel {name} = {v} ({'must be 0' if exact else '>= 1e-5'})")
    return out


# -- training ----------------------------------------------------------------

def bin_data(X, y):
    """One constructed ``lgb.Dataset`` shared by every phase: host-side
    binning is set-up, and it does not depend on what the phases vary."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    t0 = time.perf_counter()
    train_set = lgb.Dataset(X, y, params=BASE_PARAMS)
    train_set.construct(Config(BASE_PARAMS))
    log(f"data: binned {X.shape[0]}x{X.shape[1]} in "
        f"{time.perf_counter() - t0:.1f}s (set-up)")
    return train_set


def predict_held_out(bst, X) -> np.ndarray:
    """``bst.predict`` over all of ``X``, ``PREDICT_CHUNK`` rows per call.
    The dense predictor compiles one program per row bucket and XLA:TPU's
    compile time for it grows with the bucket (1 s at 4096 rows, 150 s at
    131072: PERF.md, PR 21), so the held-out rows go through one small
    bucket many times instead of one large bucket once."""
    return np.concatenate([bst.predict(X[lo:lo + PREDICT_CHUNK])
                           for lo in range(0, len(X), PREDICT_CHUNK)])


def _xla_compile_seconds(rec: dict) -> float:
    return rec["setup_seconds"].get("compile_or_load", 0.0)


def train_phase(name: str, train_set, holdout, params: dict, steps: int, *,
                quantized: bool, auc_floor: float = AUC_FLOOR,
                on_tpu: bool = True) -> dict:
    """``lgb.train`` -> asserts on what ran -> held-out ``predict`` ->
    model text round trip.  Returns the phase's facts."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.metric import _weighted_auc
    from lightgbm_tpu.ops.histogram_pallas import (resolve_interpret,
                                                   resolve_pipeline)
    from lightgbm_tpu.serve.compiler import fallback_counts

    Xh, yh = holdout
    rows = train_set.num_data()
    t0 = time.perf_counter()
    bst = lgb.train(params, train_set, steps)
    gbdt = bst._gbdt
    score = np.asarray(gbdt.score)           # waits for the device
    wall = time.perf_counter() - t0
    learner, rec = gbdt.learner, bst.train_record.snapshot()
    compile_s = _xla_compile_seconds(rec)
    pipe = resolve_pipeline(None)
    sites = sorted(rec["hist_kernel"])
    log(f"{name}: {steps} steps on {rows}x{Xh.shape[1]}, grower="
        f"{learner.grow_mode} quantized={learner.quantized} interpret="
        f"{resolve_interpret()} hist_passes_last={rec['hist_passes_last']} "
        f"kernels={sites}")
    log(f"{name}: set-up: XLA compile {compile_s:.1f}s of {wall:.1f}s wall "
        f"(upload and {steps} steps included)")

    check(learner.grow_mode == "wave", f"{name}: grower is "
          f"{learner.grow_mode}, not wave")
    check(bool(learner.quantized) == quantized,
          f"{name}: learner.quantized={learner.quantized}")
    check(resolve_interpret() == (not on_tpu),
          f"{name}: Pallas interpret mode is {resolve_interpret()}")
    want = [f"ops/hist_kernel/leaves_q8/{pipe}",
            f"ops/hist_kernel/single/{pipe}"] if quantized else \
        [f"ops/hist_kernel/leaves/{pipe}"]
    # the grower's row updates fetch their own columns (dma pipeline)
    want.append(f"ops/hist_kernel/row_update/{pipe}"
                + ("/fetch" if pipe == "dma" else ""))
    for site in want:
        check(site in sites, f"{name}: kernel site {site} never traced "
              f"(got {sites})")
    check(rec["num_trees"] == steps and rec["hist_passes_last"] > 0,
          f"{name}: {rec['num_trees']} trees, "
          f"{rec['hist_passes_last']} hist passes in the last")
    check(score.shape == (rows,) and bool(np.isfinite(score).all()),
          f"{name}: training scores not finite / wrong shape")

    fb0 = fallback_counts()
    t0 = time.perf_counter()
    pred = predict_held_out(bst, Xh)
    log(f"{name}: set-up: predict of {len(yh)} rows in calls of "
        f"{PREDICT_CHUNK}, compile included, "
        f"{time.perf_counter() - t0:.1f}s wall")
    check(pred.shape == (Xh.shape[0],) and bool(np.isfinite(pred).all())
          and 0.0 <= pred.min() and pred.max() <= 1.0,
          f"{name}: held-out predictions malformed")
    if on_tpu:
        check(fallback_counts() == fb0, f"{name}: predict left the dense "
              f"MXU program: {fallback_counts()}")
    auc = float(_weighted_auc(yh, pred, None))
    check(auc > auc_floor, f"{name}: held-out AUC {auc:.4f} <= {auc_floor}")

    # the sequential tree walk is the plain reference of the dense program
    small = Xh[:4096]
    walk = bst.to_predictor(compiler="walk").predict(small)
    check(np.allclose(pred[:len(small)], walk, atol=1e-5),
          f"{name}: dense predict vs tree walk differ by "
          f"{np.abs(pred[:len(small)] - walk).max()}")
    again = lgb.Booster(model_str=bst.model_to_string()).predict(small)
    check(np.allclose(pred[:len(small)], again, atol=1e-6),
          f"{name}: model text round trip changed predictions by "
          f"{np.abs(pred[:len(small)] - again).max()}")
    log(f"{name}: held-out AUC {auc:.4f} on {len(yh)} rows; dense == walk; "
        "model text round trip ok")
    return {"auc": auc, "compile_seconds": compile_s, "kernels": sites,
            "hist_passes_last": rec["hist_passes_last"]}


def data_parallel_phase(train_set, holdout, steps: int = DP_STEPS,
                        extra: dict | None = None, *,
                        auc_floor: float = AUC_FLOOR,
                        on_tpu: bool = True) -> dict:
    """``tree_learner=data`` over every visible chip.

    Parity first: with deterministic rounding and the speculative ramp
    off, the data-parallel and the serial learner must grow the same
    trees.  (With the ramp on they legitimately differ at this size: each
    shard strides its own rows for the provisional subsample, so the two
    verify and commit different near-best splits — learner/wave.py
    ``_spec_state``.)  Then the flagship configuration itself, ramp on,
    across the mesh: quality, and the bin matrix and per-row vectors
    sharded over all chips from construction."""
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.metric import _weighted_auc
    Xh, yh = holdout
    small = Xh[:PREDICT_CHUNK]
    flagship = {**BASE_PARAMS, **Q8_PARAMS, **(extra or {})}
    parity = {**flagship, "stochastic_rounding": False,
              "tpu_speculative_ramp": False}
    preds = {}
    for tl in ("serial", "data"):
        t0 = time.perf_counter()
        bst = lgb.train({**parity, "tree_learner": tl}, train_set, steps)
        preds[tl] = bst.predict(small)
        log(f"data: parity run tree_learner={tl}, {steps} steps + predict "
            f"in {time.perf_counter() - t0:.1f}s wall (set-up: XLA compile "
            f"{_xla_compile_seconds(bst.train_record.snapshot()):.1f}s)")
    diff = float(np.abs(preds["data"] - preds["serial"]).max())
    check(np.allclose(preds["data"], preds["serial"], atol=DP_TOL,
                      rtol=DP_TOL),
          f"data: data-parallel vs serial predictions differ by {diff}")
    del bst, preds
    gc.collect()                     # the parity boosters' device buffers

    t0 = time.perf_counter()
    bst = lgb.train({**flagship, "tree_learner": "data"}, train_set, steps)
    auc = float(_weighted_auc(yh, predict_held_out(bst, Xh), None))
    log(f"data: flagship config across the mesh, {steps} steps + predict in "
        f"{time.perf_counter() - t0:.1f}s wall; held-out AUC {auc:.4f}")
    check(auc > auc_floor, f"data: held-out AUC {auc:.4f} <= {auc_floor}")
    learner = bst._gbdt.learner
    ndev = len(jax.devices())
    check(getattr(learner, "wave", False), "data: DP learner is not wave")
    for what, arr in (("_XpT", learner._XpT), ("score", bst._gbdt.score)):
        held = {s.device for s in arr.addressable_shards}
        check(len(held) == ndev and not arr.sharding.is_fully_replicated,
              f"data: {what} lives on {len(held)} of {ndev} devices "
              f"({arr.sharding})")
    stats = [d.memory_stats() for d in jax.devices()]
    check(all(stats) or not on_tpu, "data: memory_stats() unavailable")
    used = [s["bytes_in_use"] for s in stats] if all(stats) else None
    check(used is None or max(used) < 10 * max(min(used), 1),
          f"data: per-device bytes_in_use not of one order: {used}")
    log(f"data: |data - serial| max {diff:.2e} (tol {DP_TOL}); _XpT "
        f"sharding {learner._XpT.sharding.spec} over {ndev} devices; "
        f"bytes_in_use {used}")
    return {"max_abs_diff": diff, "auc": auc, "bytes_in_use": used}


# -- entry -------------------------------------------------------------------

def main(rows: int = ROWS, holdout: int = HOLDOUT, steps: int = STEPS,
         kernel_rows: int = KERNEL_ROWS) -> int:
    dev = find_device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no chip found — JAX reports platform="
              f"{dev['platform']!r}; this script only passes on a TPU",
              file=sys.stderr)
        return 1

    from lightgbm_tpu.utils.cache import configure_compile_cache
    from lightgbm_tpu.utils.native import get_lib
    log(f"compile cache: {configure_compile_cache()}")

    t0 = time.perf_counter()
    diffs = kernel_selfcheck(kernel_rows)
    log(f"kernels: {json.dumps(diffs)} ({time.perf_counter() - t0:.1f}s "
        "wall, compile included)")

    X, y, Xh, yh = make_data(rows, holdout)
    log(f"data: {rows}+{holdout} x {FEATURES} float32 generated; native "
        f"binner {'loaded' if get_lib() is not None else 'NOT loaded (numpy)'}")
    train_set = bin_data(X, y)
    del X, y
    train_phase("q8", train_set, (Xh, yh), {**BASE_PARAMS, **Q8_PARAMS},
                steps, quantized=True)
    train_phase("exact", train_set, (Xh, yh), dict(BASE_PARAMS), steps,
                quantized=False)
    if dev["count"] > 1:
        data_parallel_phase(train_set, (Xh, yh))

    log(f"all phases passed in {time.perf_counter() - _T0:.0f}s")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
