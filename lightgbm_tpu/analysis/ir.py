"""Jaxpr IR walking — the shared traversal every trace contract rides.

The performance story of the distributed growers rests on *traced-program
shape* guarantees (one reduce_scatter per histogram-merge site, zero
full-histogram psums, ceil(log2 W) spec-ramp psums, no giant
constant-folded operands).  Before this module, three divergent ad-hoc
jaxpr walkers lived in tests/test_wave_scatter.py, tests/test_specramp.py
and tests/test_telemetry.py; they are superseded by the recursive
traversal here, which descends through every sub-jaxpr a program can
nest (pjit / while / cond branches / scan / shard_map / custom_jvp /
pallas_call kernels), so a contract checked "on the program" really sees
the whole program.

Everything here is pure inspection: no tracing side effects, no
execution.  :func:`trace` is a thin :func:`jax.make_jaxpr` wrapper kept
here so callers (tests, the lint driver) share one spelling.
"""

from __future__ import annotations

import hashlib
from typing import Any, Callable, Dict, Iterator, List, NamedTuple, Tuple

__all__ = ["EqnInfo", "subjaxprs", "iter_eqns", "walk_eqns",
           "collect_collectives", "collectives_of", "count_primitive",
           "iter_consts", "aval_elems", "max_operand_elems", "trace",
           "stable_hash", "COLLECTIVE_PRIMITIVES", "is_collective"]


# Primitive names that move bytes across the mesh axis.  ``psum2`` is the
# spelling newer jax versions give lax.psum inside shard_map; the
# substring names cover reduce_scatter/all_reduce renames across
# versions (the same tolerance tests/test_wave_scatter.py shipped).
COLLECTIVE_PRIMITIVES = ("psum", "psum2", "pmax", "pmin", "all_gather",
                         "all_to_all", "ppermute")
_COLLECTIVE_SUBSTRINGS = ("reduce_scatter", "all_reduce")


def is_collective(primitive_name: str) -> bool:
    return (primitive_name in COLLECTIVE_PRIMITIVES or
            any(s in primitive_name for s in _COLLECTIVE_SUBSTRINGS))


class EqnInfo(NamedTuple):
    """One equation seen by the recursive walk.

    ``path`` is the tuple of enclosing primitive names (e.g.
    ``("shard_map", "while")`` for an eqn inside a while-loop body inside
    a shard_map) — rules use it to tell hot-loop eqns from setup eqns.
    """

    prim: str
    eqn: Any
    path: Tuple[str, ...]

    @property
    def in_loop(self) -> bool:
        return any(p in ("while", "scan", "fori_loop") for p in self.path)


def subjaxprs(val: Any) -> Iterator[Any]:
    """Sub-jaxprs inside an eqn param: raw Jaxpr (shard_map), ClosedJaxpr
    (pjit/while/cond/scan/pallas_call) or lists of either (cond
    branches).  Yields raw Jaxpr objects."""
    if hasattr(val, "eqns"):
        yield val
    elif hasattr(val, "jaxpr"):
        yield val.jaxpr
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from subjaxprs(item)


def _as_jaxpr(jaxpr_like: Any) -> Any:
    """Accept a Jaxpr or a ClosedJaxpr."""
    return jaxpr_like.jaxpr if hasattr(jaxpr_like, "jaxpr") else jaxpr_like


def iter_eqns(jaxpr_like: Any,
              path: Tuple[str, ...] = ()) -> Iterator[EqnInfo]:
    """Every equation in the program, depth-first through all nested
    sub-jaxprs, tagged with its enclosing-primitive path."""
    jaxpr = _as_jaxpr(jaxpr_like)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield EqnInfo(name, eqn, path)
        sub_path = path + (name,)
        for val in eqn.params.values():
            for sub in subjaxprs(val):
                yield from iter_eqns(sub, sub_path)


def aval_elems(var: Any) -> int:
    """Element count of a var/literal's abstract value (0 when shapeless)."""
    aval = getattr(var, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    size = 1
    for d in aval.shape:
        size *= int(d)
    return size


def max_operand_elems(eqn: Any) -> int:
    """Largest operand (in elements) of one equation — the quantity the
    collective-shape assertions bound (a psum's wire payload is its
    operand)."""
    size = 0
    for v in eqn.invars:
        size = max(size, aval_elems(v))
    return size


def walk_eqns(jaxpr_like: Any) -> Iterator[Tuple[str, int]]:
    """Yield every ``(primitive_name, max_operand_elems)``, descending
    into while/cond/pjit/scan/shard_map sub-jaxprs (the historical
    test-local walker API, now single-sourced here)."""
    for info in iter_eqns(jaxpr_like):
        yield info.prim, max_operand_elems(info.eqn)


def count_primitive(jaxpr_like: Any, name: str) -> int:
    """Number of eqns binding the named primitive anywhere in the
    program (replaces ``str(jaxpr).count(name)`` — substring counting
    breaks the day a primitive name embeds another's)."""
    return sum(1 for info in iter_eqns(jaxpr_like) if info.prim == name)


def collectives_of(jaxpr_like: Any) -> Dict[str, List[int]]:
    """Map collective primitive name -> operand sizes (elements), one
    entry per traced collective op."""
    out: Dict[str, List[int]] = {}
    for info in iter_eqns(jaxpr_like):
        if is_collective(info.prim):
            out.setdefault(info.prim, []).append(
                max_operand_elems(info.eqn))
    return out


def trace(fn: Callable, *args, **kwargs) -> Any:
    """``jax.make_jaxpr`` — trace without executing or compiling."""
    import jax
    return jax.make_jaxpr(fn)(*args, **kwargs)


def collect_collectives(fn: Callable, *args) -> Dict[str, List[int]]:
    """Trace ``fn`` and return its collective ops by primitive name
    (tests/test_wave_scatter.py's ``_collectives_of``, single-sourced)."""
    return collectives_of(trace(fn, *args))


def iter_consts(jaxpr_like: Any) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Every closed-over constant in the program: the top-level
    ClosedJaxpr's consts plus the consts of every nested ClosedJaxpr
    (pjit bodies keep their own).  Yields ``(const, path)``."""

    def _walk(closed: Any, path: Tuple[str, ...]) -> Iterator:
        consts = getattr(closed, "consts", None)
        if consts:
            for c in consts:
                yield c, path
        jaxpr = _as_jaxpr(closed)
        if not hasattr(jaxpr, "eqns"):
            return
        for eqn in jaxpr.eqns:
            sub_path = path + (eqn.primitive.name,)
            for val in eqn.params.values():
                if hasattr(val, "jaxpr"):  # ClosedJaxpr with own consts
                    yield from _walk(val, sub_path)
                elif isinstance(val, (list, tuple)):
                    for item in val:
                        if hasattr(item, "jaxpr"):
                            yield from _walk(item, sub_path)

    yield from _walk(jaxpr_like, ())


def stable_hash(jaxpr_like: Any) -> str:
    """Content hash of a traced program.

    The pretty-printer assigns variable names deterministically in
    traversal order, so two traces of the same Python program at the
    same shapes/dtypes print identically — the hash is the
    retrace-budget currency: a changed hash across boosting iterations
    or across serve bucket re-traces means XLA will compile again."""
    text = str(_as_jaxpr(jaxpr_like))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def literal_operands(jaxpr_like: Any,
                     min_elems: int = 1) -> Iterator[Tuple[Any, EqnInfo]]:
    """Inline Literal operands of at least ``min_elems`` elements, with
    the eqn consuming them (scalar literals are the normal case; a big
    one is a constant XLA will fold at compile time)."""
    from jax.extend.core import Literal
    for info in iter_eqns(jaxpr_like):
        for v in info.eqn.invars:
            if isinstance(v, Literal) and aval_elems(v) >= min_elems:
                yield v, info
