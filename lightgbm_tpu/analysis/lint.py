"""``lint-trace``: trace the config matrix, enforce program contracts.

Drives :mod:`.ir` + :mod:`.rules` + :mod:`.spmd` over one traced (never
executed) program per supported training/serving shape:

* ``serial``     — the sequential wave grower (no mesh, no collectives);
* ``wave``       — the wave grower, Pallas kernels (interpret off-TPU);
* ``dp_scatter`` — W-shard DP wave, feature-sliced reduce-scatter merge;
* ``spec_ramp``  — DP wave + speculative ramp (the ceil(log2 W) budget);
* ``multitrain`` — the vmapped model axis over the wave grower;
* ``multitrain_mc`` — the same program at the multiclass (M, K) lane
  grid (L = M*K lanes), checking the K-scaled memory budget and that
  the wider lane count is retrace-stable;
* ``serve``      — the ensemble predictor across the SHAPE_BUCKETS
  ladder (one program per bucket, hash-stable on re-trace);
* ``serve_dense`` — the inference compiler's fused dense program
  (serve/compiler.py): bucket-ladder retrace probes plus the
  tree-sharded top-bucket program whose single score psum and
  per-shard memory are contract-checked;
* ``serve_zoo``  — the model zoo's stacked cross-model program
  (serve/zoo.py): M same-signature lanes vmapped over the dense
  program across the bucket ladder, plus the tree-sharded stacked
  top-bucket program whose ONE-psum-per-stack collective contract and
  M-scaled memory budget are machine-checked;
* ``serve_explain`` — the dense TreeSHAP explain program
  (explain/dense_shap.py) across the bucket ladder: retrace-stable per
  rung, zero while-loops in the row dimension (the whole point of the
  dense lowering), bounded by the serve/dense_explain memory budget.

Every config is traced TWICE with freshly built same-shape inputs so
the retrace rule sees real hash probes, and the telemetry collective
tally is snapshotted around each trace so the collective-budget rule
can cross-check contracts against both the tally and the jaxpr.

**World-size scaling**: the DP configs trace at any ``devices=W``.  Up
to the attached device count they run on a real submesh; past it the
trace rides a :class:`jax.sharding.AbstractMesh` (trace-only — shapes
and collectives are exact, nothing can execute), which is how the W=64
pod path is machine-checked on a laptop (ROADMAP item 1).

The report is JSON (``trace-lint-v1``) and the CLI exits 1 when any
violation is found (0 when clean) — CI runs this as a blocking step.
Each report records the jax/jaxlib version and the device/mesh shape it
traced under, so an 8-virtual-device run is distinguishable from a
real-chip run.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, \
    Sequence, Tuple

from . import ir
from .contracts import all_donation_contracts
from .rules import DEFAULT_RULES, TraceUnit, Violation, run_rules
from .spmd import SPMD_RULES

__all__ = ["MATRIX_CONFIGS", "Geometry", "TRACE_GEOMETRY", "MEM_GEOMETRY",
           "build_unit", "build_callable", "environment_info",
           "parse_kv_args", "run_lint", "main"]

MATRIX_CONFIGS = ("serial", "wave", "dp_scatter", "spec_ramp", "voting",
                  "multitrain", "multitrain_mc", "serve", "serve_dense",
                  "serve_zoo", "serve_explain", "ingest")

# every rule the matrix runs: the six PR-10 program-contract rules plus
# the SPMD-safety pair (collective-order, sharding-consistency)
ALL_RULES = tuple(DEFAULT_RULES) + tuple(SPMD_RULES)


class Geometry(NamedTuple):
    """Trace shapes for one lint pass.

    ``TRACE_GEOMETRY`` is the small-but-representative test-suite
    geometry (the endgame engages at 13 leaves / wave 4, scatter pads 6
    features to 8 blocks at k=8) — fast, used by ``lint-trace``.
    ``MEM_GEOMETRY`` is larger so the histogram working set dominates
    the row arrays and a footprint regression (an un-scattered merge, a
    doubled pool) moves the peak estimate well past curve noise — used
    by ``lint-mem``."""

    features: int = 6
    bins: int = 64
    leaves: int = 13
    wave: int = 4
    rows: int = 4096


TRACE_GEOMETRY = Geometry()
MEM_GEOMETRY = Geometry(features=64, bins=255, leaves=17, wave=16,
                        rows=8192)


def _ensure_devices(k: int) -> int:
    """Best-effort k virtual CPU devices.  Device count can only be set
    before the first jax client exists (jax raises RuntimeError after);
    then fall back to whatever is visible (a larger requested W then
    traces over an AbstractMesh — see :func:`_trace_mesh`)."""
    import jax
    try:
        jax.config.update("jax_num_cpu_devices", k)
    except RuntimeError:
        pass  # a client is live: the device count is fixed
    return min(k, len(jax.devices()))


def _trace_mesh(k: int, axis_name: str = "workers"):
    """A k-way 1-D mesh for TRACING: a real submesh when k devices are
    attached, else an AbstractMesh (trace-only — a program traced over
    it can never execute, which is exactly what the lint wants).
    Returns ``(mesh, abstract)``."""
    avail = _ensure_devices(k)
    if avail >= k:
        from ..parallel.mesh import get_mesh
        return get_mesh(k, axis_name), False
    from jax.sharding import AbstractMesh
    return AbstractMesh((k,), (axis_name,)), True


def _mk_train_args(seed: int, n: int, geom: Geometry,
                   quantized: bool = False):
    import jax.numpy as jnp
    import numpy as np
    f, b = geom.features, geom.bins
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, b - 1, (f, n)).astype(np.uint8)
    logit = (bins[0].astype(np.float32) / b - 0.5) * 3
    y = (logit + rng.randn(n) * 0.7 > 0).astype(np.float32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    mask = np.ones(n, np.float32)
    meta = (jnp.full((f,), b, jnp.int32), jnp.zeros((f,), bool),
            jnp.zeros((f,), bool), jnp.zeros((f,), jnp.int32),
            jnp.zeros((f,), jnp.float32), jnp.ones((f,), bool))
    return (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(mask)) + meta


def _mk_wave_grow(strategy, geom: Geometry, *, quantized: bool, spec: bool):
    from ..learner.wave import make_wave_grow_fn
    from ..ops.split import SplitParams
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    return make_wave_grow_fn(
        num_leaves=geom.leaves, num_features=geom.features,
        max_bins=geom.bins, max_depth=0, split_params=sp,
        hist_impl="pallas", any_cat=False, interpret=None, jit=False,
        wave_size=geom.wave, quantized=quantized, stochastic=False,
        spec_ramp=spec, spec_tol=0.02, strategy=strategy)


def _serial_entry(grow):
    def entry(bins, grad, hess, mask, nb, ic, hn, mono, cp, fm):
        return grow(bins, grad, hess, mask, nb, ic, hn, mono, cp, (), fm)
    return entry


def _dp_entry(grow, mesh, ax):
    from ..parallel.mesh import shard_wave_grower
    return shard_wave_grower(_serial_entry(grow), mesh, ax)


def _trace_with_tally(fn, args) -> Tuple[Any, Dict[str, Dict[str, Any]]]:
    """make_jaxpr plus the telemetry collective delta the trace fired."""
    from ..telemetry.train_record import collectives_snapshot
    before = collectives_snapshot()
    jaxpr = ir.trace(lambda *a: fn(*a), *args)
    after = collectives_snapshot()
    delta: Dict[str, Dict[str, Any]] = {}
    for site, rec in after.items():
        base = before.get(site, {"count": 0, "bytes": 0})
        dc = rec["count"] - base["count"]
        if dc > 0:
            delta[site] = {"op": rec["op"], "count": dc,
                           "bytes": rec["bytes"] - base["bytes"]}
    return jaxpr, delta


def _base_ctx(geom: Geometry, **kw) -> Dict[str, Any]:
    ctx: Dict[str, Any] = {
        "wave_size": geom.wave, "features": geom.features,
        "bins": geom.bins, "leaves": geom.leaves, "rows": geom.rows,
        "itemsize": 4, "nshards": 1, "world_size": 1, "quantized": False,
        "spec_ramp": False}
    from ..telemetry import _config as tele_config
    if not tele_config.enabled():
        # no tallies to cross-check against the program (the jaxpr-side
        # rules still run at full strength)
        ctx["crosscheck_tally"] = False
    ctx.update(kw)
    return ctx


def _unit_from_traces(name: str, build: Callable[[int], Tuple[Any, tuple]],
                      ctx: Dict[str, Any]) -> TraceUnit:
    """Trace a config twice (fresh same-shape args) for the retrace
    probe; rules run on the first trace's jaxpr + tally."""
    fn0, args0 = build(0)
    jaxpr0, tally = _trace_with_tally(fn0, args0)
    h0 = ir.stable_hash(jaxpr0)
    fn1, args1 = build(1)
    jaxpr1, _ = _trace_with_tally(fn1, args1)
    h1 = ir.stable_hash(jaxpr1)
    return TraceUnit(name=name, jaxpr=jaxpr0, ctx=ctx,
                     collectives=tally,
                     hashes=[("iteration", h0), ("iteration", h1)])


def _serial_builder(geom: Geometry, quantized: bool):
    from ..ops.histogram_pallas import pad_rows

    def build(i: int):
        grow = _mk_wave_grow(None, geom, quantized=quantized, spec=False)
        return _serial_entry(grow), _mk_train_args(
            i, pad_rows(geom.rows), geom, quantized)

    return build


def _dp_builder(k: int, geom: Geometry, spec: bool):
    from ..parallel.data_parallel import WaveDPStrategy
    mesh, _abstract = _trace_mesh(k)
    ax = mesh.axis_names[0]

    def build(i: int):
        grow = _mk_wave_grow(
            WaveDPStrategy(ax, nshards=k, hist_scatter=True), geom,
            quantized=True, spec=spec)
        return _dp_entry(grow, mesh, ax), _mk_train_args(
            i, k * 4096, geom, True)

    return build


def _voting_builder(k: int, geom: Geometry, top_k: int):
    """The voting-parallel wave grower (PV-Tree comms on the wave
    grower): local top-k vote, one O(W*k) id allgather, psum of the
    selected-2k histogram slices only — the config whose DCN contracts
    the W=64 abstract trace enforces."""
    from ..parallel.voting_parallel import WaveVotingStrategy
    mesh, _abstract = _trace_mesh(k)
    ax = mesh.axis_names[0]

    def build(i: int):
        grow = _mk_wave_grow(
            WaveVotingStrategy(ax, nshards=k, top_k=top_k), geom,
            quantized=True, spec=False)
        return _dp_entry(grow, mesh, ax), _mk_train_args(
            i, k * 4096, geom, True)

    return build


def _mk_ingest_chunk(geom: Geometry):
    """(fn, args) for the chunked-ingest per-chunk program: the fused
    row-update + histogram-accumulate step (ingest/grower.py) at one
    chunk of ``geom.rows`` rows.  This is the program whose footprint
    the ``ingest/chunk_pipeline`` MemoryBudget bounds — shapes are
    functions of (chunk_rows, features, bins, wave) only, which is the
    rows-independence the budget's no-rows-term contract states."""
    import jax.numpy as jnp
    import numpy as np
    from ..ingest.grower import ChunkedWaveGrower
    from ..ops.split import SplitParams

    def build(i: int):
        sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                         any_cat=False)
        gr = ChunkedWaveGrower(
            num_leaves=geom.leaves, num_features=geom.features,
            max_bins=geom.bins, max_depth=0, split_params=sp,
            num_bins=np.full(geom.features, geom.bins, np.int32),
            has_nan=np.zeros(geom.features, bool), hist_impl="segment",
            quantized=True, wave_size=geom.wave)
        W, F, B = gr.W, gr.F, gr.B
        c = geom.rows                       # one chunk's rows
        rng = np.random.RandomState(i)
        bins = jnp.asarray(rng.randint(0, B - 1, (c, F)).astype(np.uint8))
        rl = jnp.zeros((c,), jnp.uint8)
        grad = jnp.asarray(rng.randn(c).astype(np.float32))
        hess = jnp.full((c,), 0.25, jnp.float32)
        mask = jnp.ones((c,), jnp.float32)
        acc = jnp.zeros((W, F, B, 3), jnp.int32)
        zi = jnp.zeros((W,), jnp.int32)
        head = {"vals": jnp.ones((W,), jnp.float32),
                "sel_leaves": zi, "sel": jnp.ones((W,), jnp.bool_),
                "feat": zi, "thr": zi + 1, "dleft": jnp.zeros((W,),
                                                             jnp.bool_),
                "lsum": jnp.zeros((W, 3), jnp.float32),
                "rsum": jnp.ones((W, 3), jnp.float32),
                "member": jnp.zeros((W, B), jnp.bool_),
                "psum": jnp.ones((W, 3), jnp.float32),
                "new_ids": zi + 1, "node_ids": zi,
                "left_smaller": jnp.ones((W,), jnp.bool_),
                "fnan": jnp.zeros((W,), jnp.bool_),
                "f_nan_bin": zi - 1,
                "total_new": jnp.asarray(1, jnp.int32)}
        scales = (jnp.float32(0.1), jnp.float32(0.1))
        fn = lambda *a: gr._chunk_step(*a)
        return fn, (acc, bins, rl, grad, hess, mask, head, scales)

    return build


def _multitrain_builder(geom: Geometry, models: int = 3, classes: int = 1):
    def build(i: int):
        import jax
        import jax.numpy as jnp
        from ..ops.histogram_pallas import pad_rows
        grow = _mk_wave_grow(None, geom, quantized=False, spec=False)
        entry = _serial_entry(grow)
        # the model axis: per-lane grad/hess/mask over shared bins (the
        # multitrain/batched.py vm_grow shape).  Multiclass batches put
        # L = models * classes lanes on the SAME axis (batched.py's
        # (M, K) lane grid), so the multitrain_mc geometry is the same
        # program at a wider lane count — the (M, K)-scaled
        # MemoryBudget is what lint-mem checks.
        lanes = models * classes
        vm = jax.vmap(entry,
                      in_axes=(None, 0, 0, 0) + (None,) * 6)
        args = _mk_train_args(i, pad_rows(geom.rows), geom)
        stack = lambda a: jnp.stack([a * (0.5 ** m) for m in range(lanes)])
        vm_args = (args[0], stack(args[1]), stack(args[2]),
                   jnp.stack([args[3]] * lanes)) + args[4:]
        return vm, vm_args

    return build


def _mk_serve_ensemble(geom: Geometry):
    """A tiny hand-built 2-leaf/3-tree dense ensemble — the serving
    shape class, no training run needed."""
    import numpy as np
    from ..models.tree import Tree, TreeBatch, ensemble_serve_fields
    trees = []
    for t in range(3):
        trees.append(Tree(
            num_leaves=2,
            split_feature=np.array([t % geom.features], np.int32),
            threshold_bin=np.array([1], np.int32),
            nan_bin=np.array([-1], np.int32),
            threshold=np.array([0.5 + t], np.float64),
            decision_type=np.array([0], np.uint8),
            left_child=np.array([-1], np.int32),
            right_child=np.array([-2], np.int32),
            split_gain=np.array([1.0], np.float32),
            internal_value=np.array([0.0], np.float64),
            internal_weight=np.array([1.0], np.float64),
            internal_count=np.array([2], np.int64),
            leaf_value=np.array([0.1 * (t + 1), -0.1], np.float64),
            leaf_weight=np.array([1.0, 1.0], np.float64),
            leaf_count=np.array([1, 1], np.int64)))
    kind, fields, lin = ensemble_serve_fields(TreeBatch(trees))
    return ((fields, lin),), (kind,)


def _mk_serve_dense_ensemble(geom: Geometry):
    """A tiny hand-built mixed ensemble for the dense serving compiler:
    two numeric trees (one with a missing-nan/default-left node) plus a
    categorical tree whose bitset spans TWO uint32 words — the shape
    class of the fused dense program, no training run needed."""
    import numpy as np
    from ..models.tree import Tree

    def _tree(nl, sf, thr, dt, lc, rc, leaves, **kw):
        n = nl - 1
        return Tree(
            num_leaves=nl,
            split_feature=np.asarray(sf, np.int32),
            threshold_bin=np.zeros(n, np.int32),
            nan_bin=np.full(n, -1, np.int32),
            threshold=np.asarray(thr, np.float64),
            decision_type=np.asarray(dt, np.uint8),
            left_child=np.asarray(lc, np.int32),
            right_child=np.asarray(rc, np.int32),
            split_gain=np.ones(n, np.float32),
            internal_value=np.zeros(n, np.float64),
            internal_weight=np.ones(n, np.float64),
            internal_count=np.full(n, 2, np.int64),
            leaf_value=np.asarray(leaves, np.float64),
            leaf_weight=np.ones(nl, np.float64),
            leaf_count=np.ones(nl, np.int64), **kw)

    trees = [
        # numeric, 3 leaves, node1 missing-nan + default-left (dt 8|2)
        _tree(3, [0, 1], [0.5, -0.2], [0, 10], [1, -2], [-1, -3],
              [0.1, -0.2, 0.3]),
        # categorical on feature 2: rank-0 bitset over 2 words (cats
        # 1, 3 and 32 in the LEFT set)
        _tree(2, [2], [0.0], [1], [-1], [-2], [0.4, -0.4],
              cat_boundaries=np.asarray([0, 2], np.int32),
              cat_threshold=np.asarray([0b1010, 0b1], np.uint32)),
        _tree(2, [1], [1.5], [0], [-1], [-2], [-0.1, 0.2]),
        _tree(2, [0], [-0.5], [0], [-1], [-2], [0.05, -0.05]),
    ]
    return trees


def _build_serve_dense_unit(geom: Geometry, ctx: Dict[str, Any],
                            nshards: int) -> TraceUnit:
    """The fused dense serving compiler's lint unit: retrace-stability
    probes over the whole bucket ladder (unsharded) plus the
    tree-sharded program at the top bucket as the MAIN jaxpr, so the
    one-psum collective contract and the per-shard memory sweep are
    machine-checked."""
    import numpy as np
    from ..models.dense_predict import (dense_predict_raw, lower_ensemble,
                                        make_sharded_predict)
    from ..models.tree import SHAPE_BUCKETS
    # importing the compiler registers the serve/dense_predict
    # collective contract + memory budget
    from ..serve import compiler as _compiler  # noqa: F401
    trees = _mk_serve_dense_ensemble(geom)
    arrays, meta = lower_ensemble(trees, 1, geom.features)
    hashes: List[Tuple[str, str]] = []
    for bucket in SHAPE_BUCKETS:
        for rep in range(2):
            X = np.zeros((bucket, geom.features), np.float32) + rep
            jx = ir.trace(
                lambda Xa, A: dense_predict_raw(Xa, A, meta), X, arrays)
            hashes.append((f"bucket{bucket}", ir.stable_hash(jx)))
    k = max(2, min(nshards, 4))
    mesh, _abstract = _trace_mesh(k, "trees")
    sh_arrays, sh_meta = lower_ensemble(trees, 1, geom.features, shard=k)
    fn = make_sharded_predict(sh_arrays, sh_meta, mesh)
    Xtop = np.zeros((max(SHAPE_BUCKETS), geom.features), np.float32)
    jaxpr0, tally = _trace_with_tally(lambda Xa, A: fn(Xa, A),
                                      (Xtop, sh_arrays))
    jx1, _ = _trace_with_tally(lambda Xa, A: fn(Xa, A),
                               (Xtop + 1.0, sh_arrays))
    hashes.append(("sharded_top", ir.stable_hash(jaxpr0)))
    hashes.append(("sharded_top", ir.stable_hash(jx1)))
    ctx = dict(ctx)
    # one program per ladder rung plus the sharded top-bucket program
    ctx["max_distinct_programs"] = len(SHAPE_BUCKETS) + 1
    ctx["bucket"] = max(SHAPE_BUCKETS)
    ctx["trees"] = sh_arrays.path_dir.shape[0]
    ctx["leaves"] = sh_arrays.path_dir.shape[2]
    ctx["num_class"] = 1
    ctx["cat_cols"] = (0 if sh_arrays.cat_table is None
                      else sh_arrays.cat_table.shape[0])
    ctx["cat_nodes"] = (0 if sh_arrays.cat_table is None
                       else sh_arrays.cat_table.shape[1])
    ctx["nshards"] = k
    ctx["world_size"] = k
    ctx["mesh_axes"] = ("trees",)
    return TraceUnit(name="serve_dense", jaxpr=jaxpr0, ctx=ctx,
                     collectives=tally, hashes=hashes)


def _build_serve_zoo_unit(geom: Geometry, ctx: Dict[str, Any],
                          nshards: int) -> TraceUnit:
    """The zoo's stacked cross-model program: M same-signature lanes of
    the dense serving ensemble vmapped into one fused launch.  Retrace
    probes cover the whole bucket ladder (the stacked jit signature is
    fixed per (stack, bucket) — idle lanes ride zero-filled, so WHICH
    tenants are active can never force a trace); the MAIN jaxpr is the
    tree-sharded stacked top-bucket program, whose one-psum-per-STACK
    collective contract and M-scaled memory budget the rules check."""
    import numpy as np
    from ..models.dense_predict import (lower_ensemble,
                                        make_stacked_sharded_predict,
                                        stack_dense_arrays,
                                        stacked_predict_raw)
    from ..models.tree import SHAPE_BUCKETS
    # importing the zoo registers the serve/zoo_stack memory budget +
    # one-psum collective contract
    from ..serve import zoo as _zoo  # noqa: F401
    trees = _mk_serve_dense_ensemble(geom)
    m = 3
    arrays, meta = lower_ensemble(trees, 1, geom.features)
    stacked = stack_dense_arrays([arrays] * m)
    hashes: List[Tuple[str, str]] = []
    for bucket in SHAPE_BUCKETS:
        for rep in range(2):
            Xs = np.zeros((m, bucket, geom.features), np.float32) + rep
            jx = ir.trace(
                lambda Xa, S: stacked_predict_raw(Xa, S, meta),
                Xs, stacked)
            hashes.append((f"bucket{bucket}", ir.stable_hash(jx)))
    k = max(2, min(nshards, 4))
    mesh, _abstract = _trace_mesh(k, "trees")
    sh_arrays, sh_meta = lower_ensemble(trees, 1, geom.features, shard=k)
    sh_stacked = stack_dense_arrays([sh_arrays] * m)
    fn = make_stacked_sharded_predict(sh_stacked, sh_meta, mesh)
    Xtop = np.zeros((m, max(SHAPE_BUCKETS), geom.features), np.float32)
    jaxpr0, tally = _trace_with_tally(lambda Xa, S: fn(Xa, S),
                                      (Xtop, sh_stacked))
    jx1, _ = _trace_with_tally(lambda Xa, S: fn(Xa, S),
                               (Xtop + 1.0, sh_stacked))
    hashes.append(("sharded_top", ir.stable_hash(jaxpr0)))
    hashes.append(("sharded_top", ir.stable_hash(jx1)))
    ctx = dict(ctx)
    # one stacked program per ladder rung plus the sharded top bucket
    ctx["max_distinct_programs"] = len(SHAPE_BUCKETS) + 1
    ctx["models"] = m
    ctx["bucket"] = max(SHAPE_BUCKETS)
    ctx["trees"] = sh_arrays.path_dir.shape[0]
    ctx["leaves"] = sh_arrays.path_dir.shape[2]
    ctx["num_class"] = 1
    ctx["cat_cols"] = (0 if sh_arrays.cat_table is None
                       else sh_arrays.cat_table.shape[0])
    ctx["cat_nodes"] = (0 if sh_arrays.cat_table is None
                        else sh_arrays.cat_table.shape[1])
    ctx["nshards"] = k
    ctx["world_size"] = k
    ctx["mesh_axes"] = ("trees",)
    return TraceUnit(name="serve_zoo", jaxpr=jaxpr0, ctx=ctx,
                     collectives=tally, hashes=hashes)


def _mk_serve_explain(geom: Geometry):
    """(arrays, dmeta, exp, emeta) for the dense TreeSHAP program over
    the mixed serving ensemble — importing the explain compiler
    registers the serve/dense_explain memory budget the lint-mem pass
    bounds this config with."""
    from ..explain import compiler as _explain_compiler  # noqa: F401
    from ..explain.dense_shap import lower_explain
    from ..models.dense_predict import lower_ensemble
    trees = _mk_serve_dense_ensemble(geom)
    arrays, dmeta = lower_ensemble(trees, 1, geom.features)
    exp, emeta = lower_explain(trees, 1, geom.features + 1)
    return arrays, dmeta, exp, emeta


def _build_serve_explain_unit(geom: Geometry,
                              ctx: Dict[str, Any]) -> TraceUnit:
    """The explain lane's lint unit: the dense TreeSHAP program traced
    across the whole bucket ladder (retrace-stability probes per rung),
    with the top-bucket program as the MAIN jaxpr so the no-row-loop
    guarantee and the declared memory curve are machine-checked."""
    import numpy as np
    from ..explain.dense_shap import dense_explain
    from ..models.tree import SHAPE_BUCKETS
    arrays, dmeta, exp, emeta = _mk_serve_explain(geom)
    hashes: List[Tuple[str, str]] = []
    jaxpr0 = None
    tally: Dict[str, Dict[str, Any]] = {}
    for bucket in SHAPE_BUCKETS:
        for rep in range(2):
            X = np.zeros((bucket, geom.features), np.float32) + rep
            fn = lambda Xa, A, E: dense_explain(Xa, A, dmeta, E, emeta)
            jx, t = _trace_with_tally(fn, (X, arrays, exp))
            hashes.append((f"bucket{bucket}", ir.stable_hash(jx)))
            if bucket == max(SHAPE_BUCKETS):
                jaxpr0, tally = jx, t
    ctx = dict(ctx)
    # one explain program per ladder rung and not one more
    ctx["max_distinct_programs"] = len(SHAPE_BUCKETS)
    ctx["bucket"] = max(SHAPE_BUCKETS)
    ctx["trees"] = emeta.num_trees
    ctx["leaves"] = int(exp.leaf_val.shape[2])
    ctx["depth"] = emeta.depth
    ctx["num_class"] = emeta.num_class
    ctx["cols"] = emeta.num_cols
    return TraceUnit(name="serve_explain", jaxpr=jaxpr0, ctx=ctx,
                     collectives=tally, hashes=hashes)


def _build_serve_unit(geom: Geometry, ctx: Dict[str, Any]) -> TraceUnit:
    import numpy as np
    from ..models.tree import SHAPE_BUCKETS, predict_raw_ensemble
    per_class, kinds = _mk_serve_ensemble(geom)
    hashes: List[Tuple[str, str]] = []
    jaxpr0 = None
    tally: Dict[str, Dict[str, Any]] = {}
    for bucket in SHAPE_BUCKETS:
        for rep in range(2):
            X = np.zeros((bucket, geom.features), np.float32) + rep
            fn = lambda Xa, pc: predict_raw_ensemble(Xa, pc, kinds)
            jx, t = _trace_with_tally(fn, (X, per_class))
            hashes.append((f"bucket{bucket}", ir.stable_hash(jx)))
            if bucket == max(SHAPE_BUCKETS):
                jaxpr0, tally = jx, t
    ctx = dict(ctx)
    # one compiled program per ladder rung and not one more
    ctx["max_distinct_programs"] = len(SHAPE_BUCKETS)
    ctx["bucket"] = max(SHAPE_BUCKETS)
    ctx["trees"] = 3
    return TraceUnit(name="serve", jaxpr=jaxpr0, ctx=ctx,
                     collectives=tally, hashes=hashes)


def build_unit(name: str, nshards: int = 8,
               geometry: Optional[Geometry] = None) -> TraceUnit:
    """Trace one matrix config into a rule-ready :class:`TraceUnit`."""
    geom = geometry or TRACE_GEOMETRY
    if name == "serial":
        return _unit_from_traces("serial", _serial_builder(geom, False),
                                 _base_ctx(geom))
    if name == "wave":
        return _unit_from_traces("wave", _serial_builder(geom, True),
                                 _base_ctx(geom, quantized=True))
    if name == "dp_scatter":
        return _unit_from_traces(
            "dp_scatter", _dp_builder(nshards, geom, spec=False),
            _base_ctx(geom, nshards=nshards, world_size=nshards,
                      quantized=True, rows=nshards * 4096,
                      mesh_axes=("workers",)))
    if name == "spec_ramp":
        return _unit_from_traces(
            "spec_ramp", _dp_builder(nshards, geom, spec=True),
            _base_ctx(geom, nshards=nshards, world_size=nshards,
                      quantized=True, spec_ramp=True,
                      rows=nshards * 4096, mesh_axes=("workers",)))
    if name == "voting":
        # top_k=2 keeps 2k < F at the trace geometry so the voted psum
        # genuinely moves fewer bytes than the full (F,B,3) merge —
        # the ratio the DCN contracts bound
        return _unit_from_traces(
            "voting", _voting_builder(nshards, geom, top_k=2),
            _base_ctx(geom, nshards=nshards, world_size=nshards,
                      quantized=True, top_k=2, rows=nshards * 4096,
                      hosts=max(1, nshards // 8),
                      mesh_axes=("workers",)))
    if name == "multitrain":
        return _unit_from_traces("multitrain", _multitrain_builder(geom),
                                 _base_ctx(geom, models=3))
    if name == "multitrain_mc":
        return _unit_from_traces(
            "multitrain_mc", _multitrain_builder(geom, models=2, classes=3),
            _base_ctx(geom, models=2, classes=3))
    if name == "serve":
        return _build_serve_unit(geom, _base_ctx(geom))
    if name == "serve_dense":
        return _build_serve_dense_unit(geom, _base_ctx(geom), nshards)
    if name == "serve_zoo":
        return _build_serve_zoo_unit(geom, _base_ctx(geom), nshards)
    if name == "serve_explain":
        return _build_serve_explain_unit(geom, _base_ctx(geom))
    if name == "ingest":
        return _unit_from_traces(
            "ingest", _mk_ingest_chunk(geom),
            _base_ctx(geom, quantized=True, chunk_rows=geom.rows))
    raise ValueError(f"unknown lint config '{name}' "
                     f"(matrix: {', '.join(MATRIX_CONFIGS)})")


def build_callable(name: str, nshards: int = 8,
                   geometry: Optional[Geometry] = None
                   ) -> Optional[Tuple[Any, tuple]]:
    """The (fn, args) a config traces — for callers that need to
    LOWER/COMPILE it (the lint-mem XLA cross-check).  None for the mesh
    configs: XLA's ``memory_analysis()`` semantics on SPMD executables
    depend on the partition count (per-partition vs aggregate differs
    across backends/partitionings), so the compiler cross-check is
    restricted to unpartitioned programs — the mesh configs are bounded
    by their declared curves and the per-shard body sweep instead."""
    geom = geometry or TRACE_GEOMETRY
    if name in ("serial", "wave"):
        return _serial_builder(geom, name == "wave")(0)
    if name == "multitrain":
        return _multitrain_builder(geom)(0)
    if name == "multitrain_mc":
        return _multitrain_builder(geom, models=2, classes=3)(0)
    if name == "ingest":
        return _mk_ingest_chunk(geom)(0)
    if name == "serve":
        import numpy as np
        from ..models.tree import SHAPE_BUCKETS, predict_raw_ensemble
        per_class, kinds = _mk_serve_ensemble(geom)
        X = np.zeros((max(SHAPE_BUCKETS), geom.features), np.float32)
        return (lambda Xa, pc: predict_raw_ensemble(Xa, pc, kinds),
                (X, per_class))
    if name == "serve_dense":
        import numpy as np
        from ..models.dense_predict import dense_predict_raw, lower_ensemble
        from ..models.tree import SHAPE_BUCKETS
        trees = _mk_serve_dense_ensemble(geom)
        arrays, meta = lower_ensemble(trees, 1, geom.features)
        X = np.zeros((max(SHAPE_BUCKETS), geom.features), np.float32)
        return (lambda Xa, A: dense_predict_raw(Xa, A, meta), (X, arrays))
    if name == "serve_zoo":
        import numpy as np
        from ..models.dense_predict import (lower_ensemble,
                                            stack_dense_arrays,
                                            stacked_predict_raw)
        from ..models.tree import SHAPE_BUCKETS
        trees = _mk_serve_dense_ensemble(geom)
        arrays, meta = lower_ensemble(trees, 1, geom.features)
        stacked = stack_dense_arrays([arrays] * 3)
        Xs = np.zeros((3, max(SHAPE_BUCKETS), geom.features), np.float32)
        return (lambda Xa, S: stacked_predict_raw(Xa, S, meta),
                (Xs, stacked))
    if name == "serve_explain":
        import numpy as np
        from ..explain.dense_shap import dense_explain
        from ..models.tree import SHAPE_BUCKETS
        arrays, dmeta, exp, emeta = _mk_serve_explain(geom)
        X = np.zeros((max(SHAPE_BUCKETS), geom.features), np.float32)
        return (lambda Xa, A, E: dense_explain(Xa, A, dmeta, E, emeta),
                (X, arrays, exp))
    return None


def environment_info(nshards: int = 0) -> Dict[str, Any]:
    """The jax/device environment a lint report was produced under —
    reports from an 8-virtual-device CPU env must be distinguishable
    from real-chip runs."""
    import os

    import jax
    info: Dict[str, Any] = {"jax_version": jax.__version__}
    try:
        import jaxlib
        info["jaxlib_version"] = jaxlib.__version__
    except Exception:
        pass
    try:
        devs = jax.devices()
        info["backend"] = devs[0].platform
        info["device_count"] = len(devs)
        info["device_kind"] = getattr(devs[0], "device_kind", "")
        info["process_count"] = jax.process_count()
    except Exception as exc:
        info["backend"] = f"unavailable ({exc})"
        info["device_count"] = 0
    flags = os.environ.get("XLA_FLAGS", "")
    forced = "xla_force_host_platform_device_count" in flags
    try:
        forced = forced or int(getattr(jax.config, "jax_num_cpu_devices",
                                       0) or 0) > 1
    except Exception:
        pass
    info["virtual_devices"] = bool(info.get("backend") == "cpu" and forced)
    if nshards:
        info["requested_devices"] = nshards
        info["abstract_mesh"] = nshards > info.get("device_count", 0)
    return info


def _donation_unit() -> TraceUnit:
    """The declared-donation entries (score buffers), checked once."""
    # importing gbdt registers its donation contracts
    from ..models import gbdt  # noqa: F401
    return TraceUnit(name="score_update",
                     ctx={"donation_contracts":
                          tuple(all_donation_contracts().values()),
                          "crosscheck_tally": False})


def run_lint(configs: Optional[Sequence[str]] = None,
             nshards: int = 8) -> Dict[str, Any]:
    """Trace the matrix, run every rule, return the JSON-ready report."""
    configs = tuple(configs) if configs else MATRIX_CONFIGS
    units: List[TraceUnit] = []
    report_cfgs: Dict[str, Any] = {}
    for name in configs:
        t0 = time.perf_counter()
        unit = build_unit(name, nshards=nshards)
        units.append(unit)
        coll = {site: dict(rec) for site, rec in
                sorted(unit.collectives.items())}
        report_cfgs[name] = {
            "jaxpr_hash": ir.stable_hash(unit.jaxpr)
            if unit.jaxpr is not None else None,
            "eqns": sum(1 for _ in ir.iter_eqns(unit.jaxpr))
            if unit.jaxpr is not None else 0,
            "collectives": coll,
            "trace_seconds": round(time.perf_counter() - t0, 3),
        }
    units.append(_donation_unit())
    violations = run_rules(units, rules=ALL_RULES)
    # SLO-coverage check (slo_cover.py): declared objectives must key to
    # registered metric series — the note_collective-contract coverage
    # pattern applied to the SLO layer
    from .slo_cover import check_slo_coverage, slo_coverage_report
    slo_violations = check_slo_coverage()
    slo_section = slo_coverage_report(violations=slo_violations)
    violations.extend(slo_violations)
    by_cfg: Dict[str, List[Violation]] = {}
    for v in violations:
        by_cfg.setdefault(v.config, []).append(v)
    for name, entry in report_cfgs.items():
        entry["ok"] = name not in by_cfg
        entry["violations"] = [v.to_json() for v in by_cfg.get(name, [])]
    report_cfgs["score_update"] = {
        "ok": "score_update" not in by_cfg,
        "violations": [v.to_json() for v in by_cfg.get("score_update", [])],
    }
    report_cfgs["slo_coverage"] = slo_section
    from .contracts import all_contracts
    return {
        "schema": "trace-lint-v1",
        "ok": not violations,
        "num_violations": len(violations),
        "environment": environment_info(nshards),
        "rules": [r.name for r in ALL_RULES],
        "contracts": {site: {"ops": list(c.ops),
                             "declared_in": c.declared_in}
                      for site, c in sorted(all_contracts().items())},
        "configs": report_cfgs,
    }


def parse_kv_args(argv: Sequence[str]) -> Dict[str, str]:
    """The lint verbs' shared ``key=value`` CLI grammar: optional
    leading ``--``, ``-`` normalized to ``_`` in keys (``hbm-gb=`` and
    ``hbm_gb=`` both work), non-``=`` tokens ignored.  One parser for
    ``lint-trace`` and ``lint-mem`` so flag spelling cannot drift
    between the verbs."""
    out: Dict[str, str] = {}
    for arg in argv:
        if arg.startswith("--"):
            arg = arg[2:]
        if "=" not in arg:
            continue
        key, value = arg.split("=", 1)
        out[key.strip().replace("-", "_")] = value.strip()
    return out


def main(argv: Sequence[str]) -> int:
    """``python -m lightgbm_tpu lint-trace [configs=a,b] [out=report.json]
    [devices=8]`` — trace the matrix, print the JSON contract report,
    exit nonzero on any violation."""
    import json

    configs: Optional[List[str]] = None
    out_path = ""
    nshards = 8
    for key, value in parse_kv_args(argv).items():
        if key in ("configs", "config"):
            configs = [c.strip() for c in value.split(",") if c.strip()]
        elif key in ("out", "json", "json_out"):
            out_path = value
        elif key in ("devices", "nshards"):
            nshards = int(value)
    t0 = time.perf_counter()
    _ensure_devices(nshards)
    report = run_lint(configs, nshards=nshards)
    report["elapsed_seconds"] = round(time.perf_counter() - t0, 3)
    text = json.dumps(report, indent=2, sort_keys=False)
    print(text)
    if out_path:
        with open(out_path, "w") as fh:
            fh.write(text + "\n")
    if not report["ok"]:
        from ..utils.log import log_warning
        log_warning(f"lint-trace: {report['num_violations']} contract "
                    f"violation(s)")
    return 0 if report["ok"] else 1
