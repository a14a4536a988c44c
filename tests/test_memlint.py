"""Memory lint (ISSUE 11 tentpole a, lightgbm_tpu/analysis/memory.py).

Contract under test:
  * the live-range sweep estimates peak live bytes of a traced program
    (args + intermediates, transients of nested sub-jaxprs) and sizes
    shard_map bodies PER SHARD;
  * a planted footprint inflation — the un-scattered full histogram on
    the dp path — exceeds the declared ``data_parallel/wave_sliced``
    curve and fires with a site-named diagnostic, while the scattered
    program stays under it;
  * VMEM: a pallas kernel's block bytes are checked against the
    per-core ceiling;
  * the XLA ``memory_analysis()`` cross-check holds within 2x where the
    backend reports one, and a drifted estimate fires;
  * ``lint-mem`` CLI: clean exit at head, report carries the
    environment block, and the rows=/devices= fit mode answers the
    pod-scale question statically.
"""

import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.analysis import ir, lint
from lightgbm_tpu.analysis import memory as memlint
from lightgbm_tpu.analysis.contracts import memory_budget_for
from lightgbm_tpu.analysis.lint import MEM_GEOMETRY, TRACE_GEOMETRY
from lightgbm_tpu.analysis.rules import TraceUnit


# ---------------------------------------------------------------------------
# the estimator
# ---------------------------------------------------------------------------

def test_estimator_counts_args_and_intermediates():
    def f(x):
        big = jnp.concatenate([x, x, x, x])        # 4x intermediate
        return big.sum()

    est = memlint.estimate_memory(ir.trace(f, jnp.ones((1024,))))
    assert est.args_bytes == 4096
    # peak >= args + the 4x concat output
    assert est.peak_bytes >= 4096 + 4 * 4096
    assert est.peak_bytes_per_device == est.peak_bytes  # no mesh
    assert any(b.bytes == 4 * 4096 for b in est.top_buffers)


def test_estimator_nested_transient():
    """A big buffer living only INSIDE a jitted sub-program still counts
    at the call site (the transient term)."""
    def inner(x):
        blown = jnp.tile(x, (16, 1))
        return blown.sum(0)

    def f(x):
        return jax.jit(inner)(x) * 2

    est = memlint.estimate_memory(ir.trace(f, jnp.ones((512,))))
    assert est.peak_bytes >= 16 * 512 * 4


def test_estimator_shard_map_body_is_per_device():
    from jax.sharding import PartitionSpec as P
    from lightgbm_tpu.parallel.mesh import get_mesh
    mesh = get_mesh(8)
    ax = mesh.axis_names[0]
    fn = jax.shard_map(lambda x: jax.lax.psum(x * 2, ax), mesh=mesh,
                       in_specs=(P(ax),), out_specs=P(), check_vma=False)
    est = memlint.estimate_memory(
        ir.trace(lambda x: fn(x), jnp.ones((8 * 1024, 16))))
    # global sweep sees the full (8192, 16) arg; the body only its
    # (1024, 16) shard
    assert est.peak_bytes >= 8 * 1024 * 16 * 4
    assert est.peak_bytes_per_device < est.peak_bytes
    assert est.peak_bytes_per_device >= 1024 * 16 * 4


def test_pallas_kernel_vmem_recorded():
    """The wave config's pallas kernels report VMEM block bytes (and
    stay under the 16 MB/core ceiling at lint geometry)."""
    unit = lint.build_unit("wave", geometry=TRACE_GEOMETRY)
    est = memlint.estimate_memory(unit.jaxpr)
    assert est.vmem_kernels, "no pallas kernels seen in the wave program"
    assert all(0 < b <= memlint.VMEM_BYTES_PER_CORE
               for b in est.vmem_kernels.values())
    # planted: a tiny ceiling makes every kernel fire, site-named
    unit.ctx.update(check_memory=True, memory_estimate=est,
                    vmem_limit=1024)
    vs = memlint.MemoryBudgetRule().check(unit)
    vmem_vs = [v for v in vs if "VMEM" in v.message]
    assert vmem_vs and "pallas_call" in vmem_vs[0].site


# ---------------------------------------------------------------------------
# planted footprint inflation: un-scattered full histogram on dp
# ---------------------------------------------------------------------------

def _dp_estimate(hist_scatter: bool):
    from lightgbm_tpu.analysis.lint import (_dp_entry, _mk_train_args,
                                            _mk_wave_grow, _trace_mesh)
    from lightgbm_tpu.parallel.data_parallel import WaveDPStrategy
    mesh, _ = _trace_mesh(8)
    ax = mesh.axis_names[0]
    grow = _mk_wave_grow(
        WaveDPStrategy(ax, nshards=8, hist_scatter=hist_scatter),
        MEM_GEOMETRY, quantized=True, spec=False)
    fn = _dp_entry(grow, mesh, ax)
    args = _mk_train_args(0, 8 * 4096, MEM_GEOMETRY, True)
    return memlint.estimate_memory(ir.trace(lambda *a: fn(*a), *args))


def test_planted_unscattered_histogram_exceeds_budget():
    """hist_scatter=False re-inflates the post-merge histograms to full
    F on every shard; the dp_scatter budget curve must catch it with a
    diagnostic naming the budget and the offending buffers."""
    est = _dp_estimate(hist_scatter=False)
    ctx = {"rows": 8 * 4096, "features": MEM_GEOMETRY.features,
           "bins": MEM_GEOMETRY.bins, "leaves": MEM_GEOMETRY.leaves,
           "wave_size": MEM_GEOMETRY.wave, "itemsize": 4,
           "world_size": 8, "quantized": True,
           "check_memory": True, "memory_estimate": est}
    unit = TraceUnit(name="dp_scatter", jaxpr=object(), ctx=ctx)
    vs = memlint.MemoryBudgetRule().check(unit)
    assert vs, "un-scattered full histogram not flagged"
    msg = vs[0].message
    assert "data_parallel/wave_sliced" in vs[0].site
    assert "exceeds" in msg and "largest live buffers" in msg
    # the diagnostic names a concrete buffer shape, not just a number
    assert "int32" in msg


def test_scattered_dp_stays_under_budget():
    est = _dp_estimate(hist_scatter=True)
    budget = memory_budget_for("dp_scatter")
    assert budget is not None
    from lightgbm_tpu.analysis.contracts import resolve_limit
    ctx = {"rows": 8 * 4096, "features": MEM_GEOMETRY.features,
           "bins": MEM_GEOMETRY.bins, "leaves": MEM_GEOMETRY.leaves,
           "wave_size": MEM_GEOMETRY.wave, "itemsize": 4,
           "world_size": 8, "quantized": True}
    limit = resolve_limit(budget.hbm_per_device, ctx)
    assert est.peak_bytes_per_device <= limit, (
        f"scattered dp {est.peak_bytes_per_device} over budget {limit}")


def test_missing_budget_is_a_violation():
    unit = TraceUnit(name="brand_new_config", jaxpr=ir.trace(
        lambda x: x * 2, jnp.ones((4,))), ctx={"check_memory": True})
    vs = memlint.MemoryBudgetRule().check(unit)
    assert vs and "no declared MemoryBudget" in vs[0].message


def test_xla_crosscheck_drift_fires():
    """An estimate outside [0.5, 2]x of the compiler's number fails."""
    jx = ir.trace(lambda x: x * 2, jnp.ones((1024,)))
    est = memlint.estimate_memory(jx)
    unit = TraceUnit(
        name="serial", jaxpr=jx,
        ctx={"check_memory": True, "memory_estimate": est,
             "rows": 1024, "features": 1, "bins": 2, "leaves": 2,
             "wave_size": 2,
             "xla_memory": {"argument_bytes": 0, "output_bytes": 0,
                            "temp_bytes": est.peak_bytes * 100,
                            "total_bytes": est.peak_bytes * 100}})
    vs = memlint.MemoryBudgetRule().check(unit)
    assert any("drifted" in v.message and v.site == "<xla-crosscheck>"
               for v in vs), vs


# ---------------------------------------------------------------------------
# the driver + CLI + fit mode
# ---------------------------------------------------------------------------

def test_run_lint_mem_serve_clean_with_xla_crosscheck():
    """The fast config end-to-end: estimate under budget AND within 2x
    of XLA's memory_analysis (the backend reports one on CPU)."""
    report = memlint.run_lint_mem(["serve"], crosscheck=True)
    assert report["ok"], report
    entry = report["configs"]["serve"]
    assert entry["ok"]
    if "estimate_over_xla" in entry:   # backend reported an analysis
        assert 0.5 <= entry["estimate_over_xla"] <= 2.0


def test_fit_report_pod_scale():
    """The static 'will 10^8 rows fit at W=64?' answer, no tracing."""
    # budgets register at module import
    import lightgbm_tpu.multitrain.batched  # noqa: F401
    import lightgbm_tpu.serve.predictor  # noqa: F401
    ctx = {"rows": 10 ** 8, "features": 28, "bins": 255, "leaves": 255,
           "wave_size": 42, "models": 64, "itemsize": 4, "bucket": 4096,
           "world_size": 64, "nshards": 64, "quantized": True}
    fit = memlint._fit_report(ctx, hbm_gb=16.0)
    assert "data_parallel/wave_sliced" in fit["budgets"]
    dp = fit["budgets"]["data_parallel/wave_sliced"]
    assert dp["fits"] and dp["hbm_bytes_per_device"] < 1 << 30
    assert "wave/grow" in fit["budgets"]
    assert "serve/bucket_ladder" in fit["budgets"]
    assert "multitrain/stacked_state" in fit["budgets"]
    # and 10^9 rows on ONE device must NOT fit a 16 GB part
    ctx1 = dict(ctx, rows=10 ** 9, world_size=1, nshards=1)
    fit1 = memlint._fit_report(ctx1, hbm_gb=16.0)
    assert not fit1["budgets"]["wave/grow"]["fits"]
    # a curve that raises (reads a ctx key the fit ctx lacks) must fail
    # the verdict, never silently count as fitting
    from lightgbm_tpu.analysis import contracts
    contracts.memory_budget("test/raising_curve", ("nowhere",),
                            lambda c: c["no_such_ctx_key"])
    try:
        fit2 = memlint._fit_report(ctx, hbm_gb=16.0)
        assert "error" in fit2["budgets"]["test/raising_curve"]
        assert not fit2["all_fit"]
    finally:
        contracts.remove_memory_budget("test/raising_curve")


def test_lint_mem_cli_exit_and_environment(tmp_path, capsys):
    out = tmp_path / "mem.json"
    rc = memlint.main(["configs=serve", f"out={out}", "crosscheck=0"])
    capsys.readouterr()
    assert rc == 0 and out.exists()
    import json
    rep = json.loads(out.read_text())
    assert rep["schema"] == "lint-mem-v1" and rep["ok"]
    env = rep["environment"]
    assert env["jax_version"] == jax.__version__
    assert env["device_count"] >= 1 and "backend" in env
    assert "virtual_devices" in env


@pytest.mark.slow
def test_full_matrix_crosscheck_within_2x():
    """Acceptance: the whole six-config matrix runs clean at head and
    every config where the backend reports a memory analysis is within
    2x of the static estimate."""
    report = memlint.run_lint_mem(crosscheck=True)
    assert report["ok"], report
    checked = [name for name, e in report["configs"].items()
               if "estimate_over_xla" in e]
    assert checked, "no config produced an XLA cross-check"
    for name in checked:
        r = report["configs"][name]["estimate_over_xla"]
        assert 0.5 <= r <= 2.0, (name, r)
