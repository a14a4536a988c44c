"""Trace-lint subsystem (ISSUE 10 tentpole, lightgbm_tpu/analysis/).

Contract under test:
  * the shared jaxpr walker descends through pjit/while/cond/scan/
    shard_map sub-jaxprs (the API the three former test-local walkers
    migrated onto — assertions there unchanged);
  * each of the six rules FIRES on a planted violation with an
    actionable, site-named diagnostic, and stays quiet on clean
    programs;
  * `run_lint` passes on matrix configs at head and the CLI exits
    nonzero when any contract is violated.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.analysis import contracts, ir, lint
from lightgbm_tpu.analysis.rules import (CollectiveBudgetRule,
                                         ConstantFoldRule, DonationRule,
                                         DtypeRule, HostSyncRule,
                                         RetraceRule, TraceUnit)
from lightgbm_tpu.telemetry import _config as tele_config
from lightgbm_tpu.telemetry.train_record import note_collective


# ---------------------------------------------------------------------------
# ir: the shared walker
# ---------------------------------------------------------------------------

def _nested_program(x):
    def body(c, _):
        return c + 1.0, c

    def cond_true(v):
        return v * 2.0

    def cond_false(v):
        return v - 1.0

    c, ys = jax.lax.scan(body, x, None, length=3)
    c = jax.lax.cond(c[0] > 0, cond_true, cond_false, c)
    return jax.jit(lambda a: a + ys.sum(0))(c)


def test_ir_walks_nested_subjaxprs():
    jx = ir.trace(_nested_program, jnp.ones((4,)))
    prims = [info.prim for info in ir.iter_eqns(jx)]
    assert "scan" in prims and "cond" in prims and "jit" in prims
    # eqns INSIDE the scan body were visited and carry the loop path
    in_scan = [info for info in ir.iter_eqns(jx) if "scan" in info.path]
    assert in_scan and all(info.in_loop for info in in_scan)
    # the tuple API mirrors the old test-local walker
    names = [n for n, _ in ir.walk_eqns(jx)]
    assert names == prims
    assert ir.count_primitive(jx, "cond") == 1


def test_ir_descends_pallas_call_kernels():
    """The walker enumerates eqns INSIDE pallas_call kernel jaxprs
    (claimed since PR 10, pinned here): both on a synthetic kernel and
    on the real wave grower's traced program."""
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + 1.0

    def f(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)

    jx = ir.trace(f, jnp.ones((8, 128), jnp.float32))
    inside = [info for info in ir.iter_eqns(jx)
              if "pallas_call" in info.path]
    assert inside, "no eqns enumerated inside the pallas kernel jaxpr"
    prims = {info.prim for info in inside}
    assert "mul" in prims and "add" in prims
    # the real thing: the wave config's program carries pallas kernels
    # and the walker sees their interiors too
    unit = lint.build_unit("wave")
    in_kernel = [info for info in ir.iter_eqns(unit.jaxpr)
                 if "pallas_call" in info.path]
    assert in_kernel, "wave program pallas kernels not descended"


def test_ir_descends_custom_jvp_and_vjp_bodies():
    @jax.custom_jvp
    def f(x):
        return jnp.sin(x) * x

    @f.defjvp
    def f_jvp(primals, tangents):
        (x,), (t,) = primals, tangents
        return f(x), (jnp.cos(x) * x + jnp.sin(x)) * t

    jx = ir.trace(lambda x: f(x) + 1.0, jnp.ones((4,)))
    in_jvp = [info for info in ir.iter_eqns(jx)
              if any(p.startswith("custom_jvp_call") for p in info.path)]
    assert in_jvp and "sin" in {i.prim for i in in_jvp}

    @jax.custom_vjp
    def g(x):
        return jnp.tanh(x) * 3.0

    def g_fwd(x):
        return g(x), x

    def g_bwd(res, ct):
        return (ct * (1 - jnp.tanh(res) ** 2) * 3.0,)

    g.defvjp(g_fwd, g_bwd)
    jxg = ir.trace(lambda x: g(x) * 2.0, jnp.ones((4,)))
    in_vjp = [info for info in ir.iter_eqns(jxg)
              if any(p.startswith("custom_vjp_call") for p in info.path)]
    assert in_vjp and "tanh" in {i.prim for i in in_vjp}


def test_ir_stable_hash_and_consts():
    jx1 = ir.trace(_nested_program, jnp.ones((4,)))
    jx2 = ir.trace(_nested_program, jnp.ones((4,)))
    assert ir.stable_hash(jx1) == ir.stable_hash(jx2)
    assert ir.stable_hash(jx1) != ir.stable_hash(
        ir.trace(_nested_program, jnp.ones((8,))))
    big = jnp.zeros((64, 64))
    jc = ir.trace(lambda x: x + big.sum(), jnp.ones(()))
    shapes = [tuple(getattr(c, "shape", ())) for c, _ in ir.iter_consts(jc)]
    assert (64, 64) in shapes


# ---------------------------------------------------------------------------
# collective-budget rule: planted full-histogram psum / undeclared site /
# tally-vs-program drift
# ---------------------------------------------------------------------------

def _mesh8():
    from lightgbm_tpu.parallel.mesh import get_mesh
    return get_mesh(8)


def _shard_psum(fn_site, payload_shape):
    """shard_map program psum-ing one payload, tallied at ``fn_site``."""
    from jax.sharding import PartitionSpec as P
    mesh = _mesh8()
    ax = mesh.axis_names[0]

    def f(x):
        note_collective(fn_site, "psum", x)
        return jax.lax.psum(x, ax)

    return jax.shard_map(f, mesh=mesh, in_specs=(P(ax),),
                         out_specs=P(ax), check_vma=False), \
        jnp.ones((8,) + payload_shape, jnp.float32)


def _unit_for(fn, args, site_filter=None, **ctx):
    from lightgbm_tpu.telemetry.train_record import collectives_snapshot
    before = collectives_snapshot()
    jx = ir.trace(lambda *a: fn(*a), *args)
    after = collectives_snapshot()
    delta = {}
    for site, rec in after.items():
        base = before.get(site, {"count": 0, "bytes": 0})
        dc = rec["count"] - base["count"]
        if dc > 0 and (site_filter is None or site.startswith(site_filter)):
            delta[site] = {"op": rec["op"], "count": dc,
                           "bytes": rec["bytes"] - base["bytes"]}
    return TraceUnit(name="planted", jaxpr=jx, ctx=ctx, collectives=delta)


@pytest.mark.skipif(not tele_config.enabled(),
                    reason="telemetry disabled via LGBM_TPU_TELEMETRY=0")
def test_budget_rule_flags_full_histogram_psum():
    """A psum moving more bytes than the site's declared per-op budget
    — the full-histogram-leak class — fires with the site name."""
    site = "test/hist_merge"
    contracts.collective_contract(site, "psum", max_count=4,
                                  max_bytes_per_op=1024)
    try:
        fn, x = _shard_psum(site, (64, 64, 3))  # 48 KB >> 1 KB budget
        unit = _unit_for(fn, (x,), site_filter="test/")
        vs = CollectiveBudgetRule().check(unit)
        assert any(site in v.message and "bytes/op" in v.message
                   for v in vs), vs
    finally:
        contracts.remove_collective_contract(site)


@pytest.mark.skipif(not tele_config.enabled(),
                    reason="telemetry disabled via LGBM_TPU_TELEMETRY=0")
def test_budget_rule_flags_count_overrun_and_undeclared_site():
    site = "test/one_merge_only"
    contracts.collective_contract(site, "psum", max_count=1)
    try:
        from jax.sharding import PartitionSpec as P
        mesh = _mesh8()
        ax = mesh.axis_names[0]

        def f(x):
            note_collective(site, "psum", x)
            a = jax.lax.psum(x, ax)
            note_collective(site, "psum", x)
            b = jax.lax.psum(x * 2, ax)
            note_collective("test/undeclared_site", "pmax", x)
            c = jax.lax.pmax(x, ax)
            return a + b + c

        fn = jax.shard_map(f, mesh=mesh, in_specs=(P(ax),),
                           out_specs=P(ax), check_vma=False)
        unit = _unit_for(fn, (jnp.ones((16,)),), site_filter="test/")
        vs = CollectiveBudgetRule().check(unit)
        msgs = "\n".join(v.message for v in vs)
        assert "traced 2 collective(s)" in msgs and site in msgs
        assert "no declared contract" in msgs and \
            "test/undeclared_site" in msgs
    finally:
        contracts.remove_collective_contract(site)


@pytest.mark.skipif(not tele_config.enabled(),
                    reason="telemetry disabled via LGBM_TPU_TELEMETRY=0")
def test_budget_rule_flags_untallied_collective_drift():
    """A collective op in the program with NO note_collective tally:
    the contract/tally drift class."""
    from jax.sharding import PartitionSpec as P
    mesh = _mesh8()
    ax = mesh.axis_names[0]
    fn = jax.shard_map(lambda x: jax.lax.psum(x, ax), mesh=mesh,
                       in_specs=(P(ax),), out_specs=P(ax), check_vma=False)
    unit = _unit_for(fn, (jnp.ones((16,)),), site_filter="test/")
    vs = CollectiveBudgetRule().check(unit)
    assert any("drifted" in v.message and v.site == "<program>"
               for v in vs), vs


# ---------------------------------------------------------------------------
# host-sync rule: planted callback in a hot loop
# ---------------------------------------------------------------------------

def test_host_sync_rule_flags_callback_in_loop():
    def body(c, _):
        pulled = jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((), jnp.float32),
            c)
        return c + pulled, None

    def f(x):
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    unit = TraceUnit(name="planted", jaxpr=ir.trace(f, jnp.float32(1.0)))
    vs = HostSyncRule().check(unit)
    assert vs and "pure_callback" in vs[0].message
    assert "hot loop" in vs[0].message and "scan" in vs[0].message
    # a clean program stays quiet
    clean = TraceUnit(name="ok", jaxpr=ir.trace(
        lambda x: x * 2, jnp.ones((4,))))
    assert HostSyncRule().check(clean) == []


# ---------------------------------------------------------------------------
# dtype rule: planted f64 on device
# ---------------------------------------------------------------------------

def test_dtype_rule_flags_f64():
    jax.config.update("jax_enable_x64", True)
    try:
        jx = ir.trace(lambda x: x * 2.0 + 1.0,
                      np.ones((8,), np.float64))
        unit = TraceUnit(name="planted", jaxpr=jx)
        vs = DtypeRule().check(unit)
        assert vs and "float64" in vs[0].message
        # an x64-sanctioned config allowlists it
        ok = TraceUnit(name="x64ok", jaxpr=jx, ctx={"allow_f64": True})
        assert DtypeRule().check(ok) == []
    finally:
        jax.config.update("jax_enable_x64", False)


def test_dtype_rule_forbid_extra_dtypes():
    """Quantized paths can forbid f32 histogram payloads outright."""
    jx = ir.trace(lambda x: x.astype(jnp.float16) * 2,
                  jnp.ones((8,), jnp.float32))
    unit = TraceUnit(name="planted", jaxpr=jx,
                     ctx={"forbid_dtypes": ("float16",)})
    vs = DtypeRule().check(unit)
    assert vs and "float16" in vs[0].message


# ---------------------------------------------------------------------------
# constant-fold rule: planted giant constant
# ---------------------------------------------------------------------------

def test_constant_fold_rule_flags_giant_constant():
    giant = jnp.zeros((512, 257), jnp.float32)  # 131584 elems > 2**16

    def f(x):
        # the constant must meet a TRACER to enter the jaxpr (a fully
        # concrete subexpression folds at trace time already)
        return jnp.sum(x + giant)

    unit = TraceUnit(name="planted", jaxpr=ir.trace(f, jnp.float32(0.0)))
    vs = ConstantFoldRule().check(unit)
    assert vs, "giant closed-over constant not flagged"
    assert "(512, 257)" in vs[0].message and "argument" in vs[0].message
    # small constants stay quiet ...
    cst = jnp.ones((64,), jnp.float32)
    small = TraceUnit(name="ok", jaxpr=ir.trace(
        lambda x: jnp.sum(x + cst), jnp.float32(0.0)))
    assert ConstantFoldRule().check(small) == []
    # ... and the threshold is ctx-tunable in both directions
    tight = TraceUnit(name="tight", jaxpr=small.jaxpr,
                      ctx={"const_fold_max_elems": 16})
    assert ConstantFoldRule().check(tight)
    loose = TraceUnit(name="loose", jaxpr=unit.jaxpr,
                      ctx={"const_fold_max_elems": 1 << 20})
    assert ConstantFoldRule().check(loose) == []


# ---------------------------------------------------------------------------
# retrace rule: planted hash flip across same-shape traces
# ---------------------------------------------------------------------------

def test_retrace_rule_flags_unstable_program():
    # two same-shape traces of one label landing on different programs
    # (the trace-dependent-Python-value class)
    h0 = ir.stable_hash(ir.trace(lambda x: x * 2, jnp.ones((4,))))
    h1 = ir.stable_hash(ir.trace(lambda x: x + 1, jnp.ones((4,))))
    assert h0 != h1
    unit = TraceUnit(name="planted",
                     hashes=[("iteration", h0), ("iteration", h1)])
    vs = RetraceRule().check(unit)
    assert vs and "iteration" in vs[0].site and "recompiles" in vs[0].message
    stable = TraceUnit(name="ok", hashes=[("it", "aaaa"), ("it", "aaaa")])
    assert RetraceRule().check(stable) == []


def test_retrace_rule_bounds_program_ladder():
    unit = TraceUnit(name="serve",
                     hashes=[("b1", "h1"), ("b8", "h2"), ("b64", "h3")],
                     ctx={"max_distinct_programs": 2})
    vs = RetraceRule().check(unit)
    assert vs and "3 distinct compiled programs" in vs[0].message


# ---------------------------------------------------------------------------
# donation rule: planted un-aliasable donation + the real score update
# ---------------------------------------------------------------------------

def test_donation_rule_flags_unaliasable_buffer():
    def bad_update(score, delta):
        return (score + delta).astype(jnp.bfloat16)  # dtype drift!

    c = contracts.DonationContract(
        name="test/bad_score_update",
        fn_ref=lambda: jax.jit(bad_update, donate_argnums=(0,)),
        donate_argnums=(0,),
        build_args=lambda: (jnp.zeros((32,), jnp.float32),
                            jnp.zeros((32,), jnp.float32)),
        declared_in="tests.test_analysis")
    vs = DonationRule().check_contract(c, TraceUnit(name="donation"))
    assert vs and "cannot alias" in vs[0].message and \
        "test/bad_score_update" in vs[0].message


def test_donation_rule_passes_real_score_update():
    from lightgbm_tpu.models import gbdt  # noqa: F401  (registers the contract)
    cs = contracts.all_donation_contracts()
    assert "gbdt/score_update" in cs
    vs = DonationRule().check_contract(cs["gbdt/score_update"],
                                       TraceUnit(name="donation"))
    assert vs == [], vs


def test_donated_score_update_bit_identical():
    """The donated and undonated score-update entries produce the same
    bits (donation only changes buffer reuse, never math)."""
    from lightgbm_tpu.models.gbdt import (_update_score_by_leaf,
                                          _update_score_by_leaf_donated)
    rng = np.random.RandomState(0)
    score = jnp.asarray(rng.randn(257).astype(np.float32))
    rl = jnp.asarray(rng.randint(0, 7, 257).astype(np.int32))
    lv = jnp.asarray(rng.randn(7).astype(np.float32))
    want = np.asarray(_update_score_by_leaf(score, rl, lv, 1.0))
    # donate a fresh, settled copy: the XLA:CPU runtime frees donated
    # buffers under in-flight readers (the reason gbdt gates the donated
    # dispatch TPU-only), so the shared `score` must not be the donated
    # operand and nothing may be pending when the donation dispatches
    score_d = jax.block_until_ready(jnp.array(score, copy=True))
    got = np.asarray(_update_score_by_leaf_donated(score_d, rl, lv, 1.0))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# the lint driver + CLI
# ---------------------------------------------------------------------------

def test_run_lint_serial_and_serve_clean():
    report = lint.run_lint(["serial", "serve"])
    assert report["schema"] == "trace-lint-v1"
    assert report["ok"], report
    assert report["configs"]["serial"]["ok"]
    assert report["configs"]["serve"]["ok"]
    # the serve ladder is hash-stable: 5 buckets -> 5 programs max
    assert report["configs"]["score_update"]["ok"]


@pytest.mark.skipif(not tele_config.enabled(),
                    reason="telemetry disabled via LGBM_TPU_TELEMETRY=0")
def test_run_lint_dp_scatter_contracts_hold():
    """The tentpole acceptance config: one reduce_scatter per merge
    site, O(W*k) exchange, everything tallied and under contract."""
    report = lint.run_lint(["dp_scatter"])
    assert report["ok"], report["configs"]["dp_scatter"]["violations"]
    coll = report["configs"]["dp_scatter"]["collectives"]
    rs = coll.get("data_parallel/wave/hist_reduce_scatter")
    if rs is not None:  # 8 virtual devices available (conftest forces it)
        assert rs["count"] == 3  # root + wave body + endgame bank
        assert "data_parallel/wave/winner_exchange" in coll


def test_lint_cli_exit_codes(tmp_path, capsys):
    out = tmp_path / "report.json"
    rc = lint.main(["configs=serve", f"out={out}"])
    assert rc == 0 and out.exists()
    import json
    rep = json.loads(out.read_text())
    assert rep["schema"] == "trace-lint-v1" and rep["ok"]
    capsys.readouterr()

    # plant a broken donation contract -> the SAME CLI must exit nonzero
    # with a site-named diagnostic in the report
    contracts.donation_contract(
        "test/planted_bad_donation",
        lambda: jax.jit(lambda s, d: (s + d).astype(jnp.int32),
                        donate_argnums=(0,)),
        (0,),
        lambda: (jnp.zeros((16,), jnp.float32),
                 jnp.zeros((16,), jnp.float32)))
    try:
        rc = lint.main(["configs=serve", f"out={out}"])
        assert rc != 0
        rep = json.loads(out.read_text())
        assert not rep["ok"]
        msgs = json.dumps(rep["configs"]["score_update"]["violations"])
        assert "test/planted_bad_donation" in msgs
    finally:
        contracts.remove_donation_contract("test/planted_bad_donation")
    capsys.readouterr()


def test_contract_registry_covers_all_note_collective_sites():
    """Every note_collective site in the source tree has a declared
    contract — grep the tree so a new collective cannot land without
    one (the drift guard's static half)."""
    import re
    from pathlib import Path

    # contracts register at module import; pull in every declaring module
    # so this test is order-independent (it must pass in isolation too)
    import lightgbm_tpu.learner.wave  # noqa: F401
    import lightgbm_tpu.parallel.data_parallel  # noqa: F401
    import lightgbm_tpu.parallel.feature_parallel  # noqa: F401
    import lightgbm_tpu.parallel.voting_parallel  # noqa: F401
    root = Path(__file__).resolve().parent.parent / "lightgbm_tpu"
    pat = re.compile(r"note_collective\(\s*[\"']([^\"']+)[\"']")
    sites = set()
    for path in root.rglob("*.py"):
        sites.update(pat.findall(path.read_text()))
    assert sites, "note_collective sites vanished?"
    declared = set(contracts.all_contracts())
    missing = sites - declared
    assert not missing, (
        f"collective sites without a declared contract: {sorted(missing)} "
        f"— add analysis.contracts.collective_contract next to each")
