"""The jitted tree step names its phases: ``jax.named_scope``s that reach the
compiled HLO's ``op_name`` metadata, where a profiler trace (and
``chipbench/scope_reduce.py``) reads them.  Scopes are metadata only: they
cost nothing at run time and do not change the program.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.learner.wave import make_wave_grow_fn
from lightgbm_tpu.ops.histogram_pallas import pad_rows
from lightgbm_tpu.ops.split import SplitParams

F, B = 6, 64

WAVE = ("lgbm.quantize", "lgbm.wave.row_update", "lgbm.wave.hist",
        "lgbm.wave.child_out", "lgbm.wave.scan", "lgbm.wave.commit",
        "lgbm.endgame", "lgbm.endgame.row_update", "lgbm.endgame.hist",
        "lgbm.endgame.select")
# q8 as the flagship runs it: the speculative ramp in the root pass's place,
# leaf renewal after the endgame; exact here with the ramp off, so the root
CASES = {
    "q8": (dict(quantized=True, renew_leaf=True, spec_ramp=True),
           WAVE + ("lgbm.ramp", "lgbm.renew"), ("lgbm.root",)),
    "exact": (dict(quantized=False, spec_ramp=False),
              WAVE + ("lgbm.root",), ("lgbm.ramp", "lgbm.renew")),
}


def scopes_of(hlo_text: str) -> set:
    """The innermost ``lgbm.`` component of every ``op_name`` in the text."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        named = [c for c in op_name.split("/") if c.startswith("lgbm.")]
        if named:
            found.add(named[-1])
    return found


def _args(n):
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B - 1, (F, n)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.full((n,), 0.25, jnp.float32)
    return (bins, grad, hess, jnp.ones((n,), jnp.float32),
            jnp.full((F,), B, jnp.int32), jnp.zeros((F,), bool),
            jnp.zeros((F,), bool), jnp.zeros((F,), jnp.int32),
            jnp.zeros((F,), jnp.float32), (), jnp.ones((F,), bool))


@pytest.mark.parametrize("case", sorted(CASES))
def test_grower_hlo_carries_every_phase_scope(case):
    kw, want, absent = CASES[case]
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    grow = make_wave_grow_fn(
        num_leaves=13, num_features=F, max_bins=B, max_depth=0,
        split_params=sp, hist_impl="pallas", any_cat=False, interpret=True,
        jit=True, wave_size=4, stochastic=False, exact_endgame=True, **kw)
    text = grow.lower(*_args(pad_rows(3000))).compile().as_text()
    got = scopes_of(text)
    assert set(want) <= got, sorted(set(want) - got)
    assert not set(absent) & got


def test_score_update_carries_its_scope():
    from lightgbm_tpu.models.gbdt import _update_score_by_leaf
    text = _update_score_by_leaf.lower(
        jnp.zeros((64,), jnp.float32), jnp.zeros((64,), jnp.int32),
        jnp.zeros((7,), jnp.float32), 0.1).compile().as_text()
    assert scopes_of(text) == {"lgbm.score_update"}


def test_score_update_kernel_carries_its_scope():
    """The select lowering (PR 35): the kernel's operations sit in the
    gather's scope, so a trace books them under ``score_renew_``; its name
    is no ``lgbm_hist_`` one, which a trace would count as histogram
    work."""
    from lightgbm_tpu.models.gbdt import _score_select_impl
    text = jax.jit(_score_select_impl).lower(
        jnp.zeros((64,), jnp.float32), jnp.zeros((4096,), jnp.int32),
        jnp.zeros((7,), jnp.float32), 0.1).compile().as_text()
    assert scopes_of(text) == {"lgbm.score_update"}
    assert kernel_scopes(text) == {
        "lgbm_score_update_l7_kr128_n64": {"lgbm.score_update"}}


def test_a_scope_outside_any_jit_names_nothing():
    """Why the eager per-tree ops (objective gradients, the learner's row
    padding, the one-time layout) carry no ``lgbm.`` name: each eager
    primitive compiles as a module of its own, whose trace starts from an
    empty name stack.  A scope takes effect only inside a jitted function."""
    f = jax.jit(lambda a: jnp.exp(a) * 2.0)
    with jax.named_scope("lgbm.outside"):
        text = f.lower(jnp.ones((8,))).compile().as_text()
    assert scopes_of(text) == set()


def kernel_scopes(hlo_text: str) -> dict:
    """Kernel name -> the innermost ``lgbm.`` scopes its operations carry
    (an interpreted kernel's operations keep the ``pallas_call``'s name)."""
    found = {}
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        kernel = re.search(r"lgbm_(hist|score_update)_[a-z0-9_]+", op_name)
        if kernel:
            named = [c for c in op_name.split("/") if c.startswith("lgbm.")]
            found.setdefault(kernel.group(0), set()).add(
                named[-1] if named else "")
    return found


@pytest.mark.parametrize("case", sorted(CASES))
def test_compaction_kernel_is_listed_and_carries_its_callers_scope(case):
    """The ``dma`` pipeline's wave and endgame passes compact their rows
    first: the compaction kernel (``lgbm_hist_`` like the kernels it
    feeds, so a trace counts it with them) and the leaf kernel behind it
    sit in the ``.hist`` scope of the site that called them, and the
    verify / root pass keeps the direct call on all N rows."""
    from lightgbm_tpu.ops.histogram_pallas import traced_kernels
    kw, _, _ = CASES[case]
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0,
                     any_cat=False)
    grow = make_wave_grow_fn(
        num_leaves=13, num_features=F, max_bins=B, max_depth=0,
        split_params=sp, hist_impl="pallas", any_cat=False, interpret=True,
        jit=True, wave_size=4, stochastic=False, exact_endgame=True,
        pipeline="dma", **kw)
    n = pad_rows(3000)
    scopes = kernel_scopes(grow.lower(*_args(n)).compile().as_text())
    sparse = {"lgbm.wave.hist", "lgbm.endgame.hist"}
    compact = [k for k in scopes if k.startswith("lgbm_hist_compact_dma_")]
    assert len(compact) == 1 and compact[0] in traced_kernels(), scopes
    assert compact[0].endswith(f"_n{n}") and scopes[compact[0]] == sparse
    # the lanes' places, a kernel of its own in front of it
    plan = [k for k in scopes if k.startswith("lgbm_hist_compact_plan_")]
    assert len(plan) == 1 and scopes[plan[0]] == sparse, scopes
    leaves = {k: v for k, v in scopes.items()
              if k.startswith("lgbm_hist_leaves")}
    # behind the compaction the leaf kernel reads the compacted arrays
    # (some lanes more than N); on all N rows it is the first pass only
    assert [v for k, v in leaves.items() if not k.endswith(f"_n{n}")] == \
        [sparse], leaves
    assert [v for k, v in leaves.items() if k.endswith(f"_n{n}")] == \
        [{"lgbm.ramp" if kw["spec_ramp"] else "lgbm.root"}], leaves
