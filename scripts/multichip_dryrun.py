"""Multichip dryrun + collective-bytes snapshot for CI.

Runs the driver's ``dryrun_multichip`` (every parallel learner compiled
and executed on an N-virtual-CPU-device mesh, DP == serial parity
asserted) and then traces the DP wave grower in BOTH histogram-merge
modes to record the scatter-vs-allreduce byte budget from the telemetry
collective tally — so the ratio the round-8 optimisation claims
(PERF.md) is tracked per push as a CI artifact.

Usage: python scripts/multichip_dryrun.py [--devices 8] [--out multichip.json]
"""

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def collective_bytes_snapshot(n_devices: int) -> dict:
    """Trace the DP wave grower with scatter on/off and diff the
    telemetry collective tallies (trace-time, no execution needed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.learner.wave import make_wave_grow_fn
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy
    from lightgbm_tpu.parallel.mesh import get_mesh, shard_wave_grower
    from lightgbm_tpu.parallel.voting_parallel import WaveVotingStrategy
    from lightgbm_tpu.telemetry.train_record import (collectives_reset,
                                                     collectives_snapshot)

    f, b, n = 8, 64, n_devices * 4096
    top_k = 2                        # 2k=4 < F=8: real voted filtering
    rng = np.random.RandomState(0)
    args = (jnp.asarray(rng.randint(0, b - 1, (f, n)).astype(np.uint8)),
            jnp.asarray(rng.randn(n).astype(np.float32)),
            jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.full((f,), b, jnp.int32), jnp.zeros((f,), bool),
            jnp.zeros((f,), bool), jnp.zeros((f,), jnp.int32),
            jnp.zeros((f,), jnp.float32), jnp.ones((f,), bool))
    mesh = get_mesh(n_devices)
    ax = mesh.axis_names[0]
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    out = {}
    strategies = {
        "scatter": WaveDPStrategy(ax, nshards=n_devices,
                                  hist_scatter=True),
        "allreduce": WaveDPStrategy(ax, nshards=n_devices),
        "voting": WaveVotingStrategy(ax, nshards=n_devices, top_k=top_k),
    }
    for mode, strategy in strategies.items():
        grow = make_wave_grow_fn(
            num_leaves=15, num_features=f, max_bins=b, max_depth=0,
            split_params=sp, hist_impl="pallas", any_cat=False,
            interpret=True, jit=False, wave_size=4, stochastic=False,
            quantized=True, strategy=strategy)
        wrapped = shard_wave_grower(
            lambda X_T, g, h, m, nb, ic, hn, mono, cp, fm: grow(
                X_T, g, h, m, nb, ic, hn, mono, cp, (), fm), mesh, ax)
        collectives_reset()
        jax.make_jaxpr(lambda *a: wrapped(*a))(*args)
        out[mode] = collectives_snapshot()
    collectives_reset()

    def per_pass(snap, site):
        rec = snap.get(site)
        return rec["bytes"] / rec["count"] if rec else None

    sc = per_pass(out["scatter"], "data_parallel/wave/hist_reduce_scatter")
    ar = per_pass(out["allreduce"], "data_parallel/wave/hist_psum")
    vo = per_pass(out["voting"], "voting_parallel/wave/voted_hist_psum")
    vo_ids = per_pass(out["voting"], "voting_parallel/wave/vote_allgather")
    out["hist_bytes_per_pass"] = {"scatter": sc, "allreduce": ar,
                                  "voting": vo, "voting_ids": vo_ids}
    out["hist_bytes_ratio_allreduce_over_scatter"] = (
        round(ar / sc, 3) if sc and ar else None)
    # PV-Tree acceptance: voted-2k*B slices vs the full-F*B merge, PER
    # LEAF — every voted psum moves exactly sel*B*3 ints per candidate
    # leaf against the allreduce merge's F*B*3, so the per-leaf ratio is
    # 2k/F.  Derive the per-leaf payloads from the tallied totals (both
    # must divide exactly; a full-F histogram leaking into the voting
    # program breaks the divisibility and fails the gate), and record
    # the raw per-pass total ratio too — the voting program psums BOTH
    # children where allreduce psums the smaller child only, so its
    # per-pass total carries more (cheap) leaves.
    sel = min(2 * top_k, f)
    leaf_vo = sel * b * 3 * 4       # voted bytes per candidate leaf
    leaf_ar = f * b * 3 * 4         # full-merge bytes per leaf
    vo_tot = out["voting"].get("voting_parallel/wave/voted_hist_psum",
                               {}).get("bytes", 0)
    ar_tot = out["allreduce"].get("data_parallel/wave/hist_psum",
                                  {}).get("bytes", 0)
    ratio_budget = sel / f
    per_leaf_ratio = leaf_vo / leaf_ar
    out["hist_bytes_ratio_voting_over_allreduce_total"] = (
        round(vo / ar, 4) if vo and ar else None)
    out["hist_bytes_per_leaf"] = {"voting": leaf_vo, "allreduce": leaf_ar,
                                  "ratio": round(per_leaf_ratio, 4)}
    out["voting_ratio_ok"] = bool(
        vo_tot and ar_tot and vo_tot % leaf_vo == 0
        and ar_tot % leaf_ar == 0
        and per_leaf_ratio <= ratio_budget + 1e-9)
    out["voting_ratio_budget_2k_over_f"] = ratio_budget
    return out


def contract_sweep_per_w(ws=(4, 8, 64)) -> dict:
    """Re-parameterized contract sweep: run the full rule matrix (the
    collective budgets + the SPMD-safety pair) over the DP configs at
    W in ``ws`` — real virtual-device submeshes up to the attached
    count, trace-only AbstractMesh past it (W=64).  One declaration set
    covers every W; this sweep proves it per push (ROADMAP item 1's
    "pod path machine-checked like the single-host one")."""
    from lightgbm_tpu.analysis import lint
    from lightgbm_tpu.analysis.lint import ALL_RULES
    from lightgbm_tpu.analysis.rules import run_rules

    out = {"schema": "contracts-per-w-v1",
           "environment": lint.environment_info(),
           "worlds": {}}
    for w in ws:
        entry = {}
        for cfg in ("dp_scatter", "spec_ramp", "voting"):
            t0 = time.perf_counter()
            unit = lint.build_unit(cfg, nshards=w)
            vs = run_rules([unit], rules=ALL_RULES)
            entry[cfg] = {
                "ok": not vs,
                "violations": [v.to_json() for v in vs],
                "collectives": {site: dict(rec) for site, rec in
                                sorted(unit.collectives.items())},
                "trace_seconds": round(time.perf_counter() - t0, 2),
            }
        out["worlds"][f"W{w}"] = entry
    out["ok"] = all(c["ok"] for e in out["worlds"].values()
                    for c in e.values())
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--out", default="multichip.json")
    ap.add_argument("--per-w-out", default="contracts-per-w.json",
                    help="per-world-size contract sweep report "
                         "(W=4/8 virtual devices, W=64 trace-only)")
    ns = ap.parse_args()

    rec = {"schema": "multichip-dryrun-v1", "n_devices": ns.devices,
           "ok": False}
    t0 = time.perf_counter()
    try:
        import __graft_entry__
        __graft_entry__.dryrun_multichip(ns.devices)
        rec["ok"] = True
    except Exception:  # noqa: BLE001 — the artifact must always be written
        rec["error"] = traceback.format_exc(limit=20)
    rec["dryrun_seconds"] = round(time.perf_counter() - t0, 2)
    try:
        rec["collectives"] = collective_bytes_snapshot(ns.devices)
    except Exception:  # noqa: BLE001
        rec["collectives_error"] = traceback.format_exc(limit=20)
    per_w_ok = True
    try:
        per_w = contract_sweep_per_w()
        per_w_ok = per_w["ok"]
        with open(ns.per_w_out, "w") as fh:
            json.dump(per_w, fh, indent=2, default=str)
    except Exception:  # noqa: BLE001
        per_w_ok = False
        with open(ns.per_w_out, "w") as fh:
            json.dump({"schema": "contracts-per-w-v1", "ok": False,
                       "error": traceback.format_exc(limit=20)}, fh,
                      indent=2)
    rec["contracts_per_w_ok"] = per_w_ok
    voting_ok = rec.get("collectives", {}).get("voting_ratio_ok", False)
    with open(ns.out, "w") as fh:
        json.dump(rec, fh, indent=2, default=str)
    print(json.dumps({k: rec[k] for k in ("ok", "dryrun_seconds")} |
                     {"ratio": rec.get("collectives", {}).get(
                         "hist_bytes_ratio_allreduce_over_scatter"),
                      "voting_ratio_per_leaf": rec.get(
                          "collectives", {}).get(
                          "hist_bytes_per_leaf", {}).get("ratio"),
                      "voting_ratio_ok": voting_ok,
                      "contracts_per_w_ok": per_w_ok}))
    return 0 if rec["ok"] and per_w_ok and voting_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
