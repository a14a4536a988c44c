"""SPMD-safety lint (ISSUE 11 tentpole b, lightgbm_tpu/analysis/spmd.py).

Contract under test:
  * ``collective_trace`` extracts the ordered per-axis collective
    schedule of a program;
  * a planted divergent-collective conditional arm fires with a
    site-named diagnostic (the static cross-host deadlock), identical
    arms stay quiet;
  * a planted shard_map mesh/spec mismatch fires;
  * the real DP configs pass both SPMD rules, and ALL existing
    collective contracts hold when checked at W=4, W=8 and W=64 (the
    last trace-only over an AbstractMesh);
  * the lint-trace report records the jax version and device/mesh
    environment it traced under (8-virtual-device runs distinguishable
    from real-chip runs).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from lightgbm_tpu.analysis import ir, lint, spmd
from lightgbm_tpu.analysis.lint import ALL_RULES
from lightgbm_tpu.analysis.rules import TraceUnit, run_rules
from lightgbm_tpu.parallel.mesh import get_mesh
from lightgbm_tpu.telemetry import _config as tele_config


def _mesh8(axis_name="workers"):
    return get_mesh(8, axis_name)


# ---------------------------------------------------------------------------
# collective_trace
# ---------------------------------------------------------------------------

def test_collective_trace_orders_ops():
    mesh = _mesh8()
    ax = mesh.axis_names[0]

    def f(x):
        a = jax.lax.psum(x, ax)
        b = jax.lax.pmax(a, ax)
        return jax.lax.psum(b * 2, ax)

    fn = jax.shard_map(f, mesh=mesh, in_specs=(P(ax),), out_specs=P(ax),
                       check_vma=False)
    seq = spmd.collective_trace(ir.trace(fn, jnp.ones((16, 4))))
    assert [op[0] for op in seq] == ["psum", "pmax", "psum"]
    assert all("workers" in op[1] for op in seq)
    assert seq[0][2] == (2, 4)          # per-shard wire shape


# ---------------------------------------------------------------------------
# collective-order: planted divergent arms
# ---------------------------------------------------------------------------

def _cond_program(divergent: bool):
    mesh = _mesh8()
    ax = mesh.axis_names[0]

    def arm_with_psum(v):
        return jax.lax.psum(v, ax)

    def arm_identity(v):
        return v * 2.0

    def f(x):
        pred = x.sum() > 0
        other = arm_identity if divergent else arm_with_psum
        return jax.lax.cond(pred, arm_with_psum, other, x)

    return jax.shard_map(f, mesh=mesh, in_specs=(P(ax),),
                         out_specs=P(ax) if divergent else P(),
                         check_vma=False)


def test_divergent_cond_arm_fires():
    fn = _cond_program(divergent=True)
    unit = TraceUnit(name="planted",
                     jaxpr=ir.trace(fn, jnp.ones((16,))))
    vs = spmd.CollectiveOrderRule().check(unit)
    assert vs, "divergent collective arms not flagged"
    assert "DIVERGENT" in vs[0].message and "deadlock" in vs[0].message
    assert "psum" in vs[0].message and "cond" in vs[0].site


def test_identical_cond_arms_quiet():
    fn = _cond_program(divergent=False)
    unit = TraceUnit(name="ok", jaxpr=ir.trace(fn, jnp.ones((16,))))
    assert spmd.CollectiveOrderRule().check(unit) == []


# ---------------------------------------------------------------------------
# sharding-consistency: planted mesh/spec mismatch
# ---------------------------------------------------------------------------

def test_shard_map_mesh_mismatch_fires():
    """A program sharded over axis 'model' while the config declares a
    ('workers',) mesh — the launcher would never build it."""
    mesh = get_mesh(4, axis_name="model")
    fn = jax.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                       in_specs=(P("model"),), out_specs=P(), check_vma=False)
    unit = TraceUnit(name="planted",
                     jaxpr=ir.trace(fn, jnp.ones((8, 2))),
                     ctx={"mesh_axes": ("workers",)})
    vs = spmd.ShardingConsistencyRule().check(unit)
    assert vs, "mesh-axis mismatch not flagged"
    assert "('model',)" in vs[0].message and "('workers',)" in vs[0].message
    assert "shard_map" in vs[0].site


def test_shard_map_matching_mesh_quiet():
    mesh = _mesh8()
    ax = mesh.axis_names[0]
    fn = jax.shard_map(lambda x: jax.lax.psum(x, ax), mesh=mesh,
                       in_specs=(P(ax),), out_specs=P(), check_vma=False)
    unit = TraceUnit(name="ok", jaxpr=ir.trace(fn, jnp.ones((16,))),
                     ctx={"mesh_axes": ("workers",)})
    assert spmd.ShardingConsistencyRule().check(unit) == []


# ---------------------------------------------------------------------------
# the real programs, across world sizes
# ---------------------------------------------------------------------------

def test_dp_unit_passes_spmd_rules():
    unit = lint.build_unit("dp_scatter")
    vs = [v for r in spmd.SPMD_RULES for v in r.check(unit)]
    assert vs == [], vs


@pytest.mark.skipif(not tele_config.enabled(),
                    reason="telemetry disabled via LGBM_TPU_TELEMETRY=0")
@pytest.mark.parametrize("w", [4, 64])
def test_contracts_hold_at_world_size(w):
    """The re-parameterized contracts: the same declarations pass at a
    real W=4 submesh and a trace-only W=64 AbstractMesh (W=8 is the
    whole-suite default exercised by test_analysis.py)."""
    for cfg in ("dp_scatter", "spec_ramp", "voting"):
        unit = lint.build_unit(cfg, nshards=w)
        assert unit.ctx["world_size"] == w
        vs = run_rules([unit], rules=ALL_RULES)
        assert vs == [], (w, cfg, vs)
        rs = unit.collectives.get("data_parallel/wave/hist_reduce_scatter")
        if rs is not None:
            assert rs["count"] == (3 if cfg == "dp_scatter" else 5)
        if cfg == "voting":
            # PV-Tree wire shape: an id all_gather and a voted-slice
            # psum per merge site, with the modeled DCN split bounded
            # by the contracts the rules just enforced
            ag = unit.collectives["voting_parallel/wave/vote_allgather"]
            vp = unit.collectives["voting_parallel/wave/voted_hist_psum"]
            assert ag["count"] == vp["count"] == 3


def test_w64_traces_over_abstract_mesh():
    """W past the attached device count must still produce a full
    program trace (shapes + collectives exact, nothing executable)."""
    mesh, abstract = lint._trace_mesh(64)
    assert abstract, "expected an AbstractMesh for W=64 on this host"
    unit = lint.build_unit("dp_scatter", nshards=64)
    shard_maps = [i for i in ir.iter_eqns(unit.jaxpr)
                  if i.prim == "shard_map"]
    assert shard_maps
    # the traced per-shard row count reflects the 64-way split
    body = shard_maps[0].eqn.params["jaxpr"]
    row_args = [tuple(v.aval.shape) for v in body.invars
                if getattr(v.aval, "ndim", 0) == 1]
    assert (4096,) in row_args          # 64*4096 global / 64 shards


# ---------------------------------------------------------------------------
# report environment (the 'which env traced this?' fix)
# ---------------------------------------------------------------------------

def test_lint_trace_report_records_environment():
    report = lint.run_lint(["serve"])
    env = report["environment"]
    assert env["jax_version"] == jax.__version__
    assert env["device_count"] >= 1
    assert env["backend"] in ("cpu", "tpu", "gpu")
    assert isinstance(env["virtual_devices"], bool)
    # the SPMD rules are part of the shipped matrix
    assert "collective-order" in report["rules"]
    assert "sharding-consistency" in report["rules"]
