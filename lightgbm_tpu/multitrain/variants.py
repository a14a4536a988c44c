"""Variant normalization and same-structure grouping for ``train_many``.

A *variant* is a per-model parameter override dict.  Two classes of
parameters can vary inside ONE compiled batch:

* **traced sweepables** (``TRACED_SWEEP``): regularization /
  split-threshold scalars that flow only through jnp arithmetic in the
  split scan (ops/split.py ``TRACEABLE_PARAMS``).  They ride a
  ``(M, S)`` array through the vmapped grower, so variants differing in
  them share one executable.
* **host sweepables** (``HOST_SWEEP``): parameters consumed purely on
  the host side of the boosting loop — sampling seeds/fractions (the
  masks they produce are per-model *inputs* to the device step),
  learning_rate (a traced ``(M,)`` scalar applied at the score update),
  early-stopping knobs and metric choice (host bookkeeping only).

Everything else is **structural**: it changes the traced program
(num_leaves, max_bin, objective, grower mode, ...) or host behavior in
ways the batch cannot express.  Variants are grouped by their structural
fingerprint; each group trains as one vmapped batch and the remainder
falls back to sequential ``train()`` calls.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..config import Config, resolve_param_aliases
from ..ops.split import TRACEABLE_PARAMS
from ..utils.random import model_stream_seed

__all__ = ["TRACED_SWEEP", "HOST_SWEEP", "SWEEPABLE", "normalize_variants",
           "structure_key", "group_variants"]

# sweepable along the traced model axis (see ops/split.py)
TRACED_SWEEP: Tuple[str, ...] = TRACEABLE_PARAMS

# sweepable host-side (per-model masks / seeds / bookkeeping); the GOSS
# rates and DART drop knobs are per-lane draws too (gbdt.goss_sample_np,
# the host face of the jitted sampler / the per-lane drop bookkeeping in
# batched._ModelState), so they sweep
# inside one batch — boosting TYPE itself stays structural
HOST_SWEEP: Tuple[str, ...] = (
    "learning_rate", "bagging_seed", "bagging_fraction",
    "pos_bagging_fraction", "neg_bagging_fraction", "feature_fraction",
    "feature_fraction_seed", "seed", "extra_seed",
    "early_stopping_round", "first_metric_only", "metric",
    "top_rate", "other_rate",
    "drop_rate", "max_drop", "skip_drop", "uniform_drop",
    "xgboost_dart_mode", "drop_seed",
)

SWEEPABLE: Tuple[str, ...] = TRACED_SWEEP + HOST_SWEEP

# seeds that replicas=M derives per model (recorded INTO the variant
# params so ``train(variant_params_m)`` is the exact standalone
# counterpart of batch model m)
_REPLICA_SEED_KEYS = ("seed", "bagging_seed", "feature_fraction_seed",
                      "extra_seed")


def normalize_variants(base_params: Dict[str, Any],
                       variants: Optional[Sequence[Dict[str, Any]]],
                       replicas: Optional[int] = None,
                       num_models: Optional[int] = None
                       ) -> List[Dict[str, Any]]:
    """Expand the user's variant spec into canonical per-model FULL param
    dicts (aliases resolved, base params merged).

    ``variants`` may be a list of override dicts or a dict of
    ``param -> list`` columns (all the same length, zipped per model).
    ``replicas=M`` spawns M bagging-decorrelated copies of the base
    params via :func:`~lightgbm_tpu.utils.random.model_stream_seed` —
    the derived seeds are materialized into each variant so model m's
    standalone counterpart is ``train(variants[m])`` verbatim."""
    base = resolve_param_aliases(base_params or {})
    if variants is not None and replicas is not None:
        raise ValueError("pass either variants or replicas, not both")
    if variants is None and replicas is None:
        m = int(num_models or 1)
        out = [dict(base) for _ in range(m)]
        return out
    if replicas is not None:
        cfg = Config(base)
        out = []
        for m in range(int(replicas)):
            v = dict(base)
            for key in _REPLICA_SEED_KEYS:
                v[key] = model_stream_seed(int(getattr(cfg, key)), m)
            out.append(v)
        return out
    if isinstance(variants, dict):
        cols = {k: list(v) for k, v in variants.items()}
        lens = {len(v) for v in cols.values()}
        if len(lens) != 1:
            raise ValueError(f"variant columns have differing lengths: "
                             f"{ {k: len(v) for k, v in cols.items()} }")
        m = lens.pop()
        variants = [{k: cols[k][i] for k in cols} for i in range(m)]
    out = []
    for v in variants:
        v = resolve_param_aliases(dict(v))
        out.append({**base, **v})
    return out


def _hashable(v: Any) -> Any:
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def structure_key(full_params: Dict[str, Any]) -> Tuple:
    """Hashable fingerprint of everything that is NOT sweepable inside a
    batch.  Variants with equal keys share one traced program."""
    skip = set(SWEEPABLE)
    return tuple(sorted((k, _hashable(v)) for k, v in full_params.items()
                        if k not in skip))


def group_variants(variant_params: List[Dict[str, Any]]
                   ) -> List[List[int]]:
    """Group variant indices by structural fingerprint, preserving the
    first-seen order of groups and the variant order within a group."""
    groups: Dict[Tuple, List[int]] = {}
    order: List[Tuple] = []
    for i, p in enumerate(variant_params):
        key = structure_key(p)
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)
    return [groups[k] for k in order]
