"""Objective base class (reference include/LightGBM/objective_function.h:19)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..dataset import Metadata

EPS = 1e-15


class ObjectiveFunction:
    """Base: holds device copies of label/weight and exposes gradient math.

    Subclasses implement ``_grad_hess(score) -> (grad, hess)`` over device
    arrays; scores and gradients are (N,) float32, or (N, K) for multiclass.
    """

    name = "base"
    is_constant_hessian = False
    need_group = False

    def __init__(self, config) -> None:
        self.config = config
        self.label: Optional[jnp.ndarray] = None
        self.weight: Optional[jnp.ndarray] = None
        self.num_data = 0

    # -- lifecycle (reference ObjectiveFunction::Init) -----------------------
    def init(self, metadata: Metadata, num_data: int) -> None:
        if metadata.label is None:
            raise ValueError(f"objective {self.name} requires labels")
        self.check_label(metadata.label)
        self.label = jnp.asarray(metadata.label, jnp.float32)
        self.weight = (jnp.asarray(metadata.weight, jnp.float32)
                       if metadata.weight is not None else None)
        self.num_data = num_data

    def place_rows(self, put) -> None:
        """Re-place the per-row device arrays with ``put`` (the boosting
        driver's row-sharded placement under tree_learner=data/voting)."""
        self.label = put(self.label)
        if self.weight is not None:
            self.weight = put(self.weight)

    def check_label(self, label: np.ndarray) -> None:
        pass

    def record(self) -> dict:
        """What the objective laid out at ``init``, as the run's
        ``TrainRecord`` states it (``snapshot()["objective"]``); {} where
        it lays out nothing beyond the rows."""
        return {}

    @property
    def num_model_per_iteration(self) -> int:
        return 1

    # -- gradients (reference GetGradients, objective_function.h:37) ---------
    def get_gradients(self, score: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        grad, hess = self._grad_hess(score)
        if self.weight is not None:
            w = self.weight if grad.ndim == 1 else self.weight[:, None]
            grad, hess = grad * w, hess * w
        return grad.astype(jnp.float32), hess.astype(jnp.float32)

    def _grad_hess(self, score):
        raise NotImplementedError

    # -- init score (reference BoostFromScore) -------------------------------
    def boost_from_score(self, class_id: int = 0) -> float:
        return 0.0

    # -- output transform (reference ConvertOutput) --------------------------
    def convert_output(self, score: jnp.ndarray) -> jnp.ndarray:
        return score

    def _np_label(self) -> np.ndarray:
        return np.asarray(self.label)

    def _np_weight(self) -> Optional[np.ndarray]:
        return None if self.weight is None else np.asarray(self.weight)


def weighted_mean(values: np.ndarray, weights: Optional[np.ndarray]) -> float:
    if weights is None:
        return float(np.mean(values))
    return float(np.sum(values * weights) / np.sum(weights))


def weighted_percentile(values: np.ndarray, weights: Optional[np.ndarray],
                        alpha: float) -> float:
    """Weighted percentile (reference regression_objective.hpp:24
    ``PercentileFun``/``WeightedPercentileFun``)."""
    order = np.argsort(values, kind="stable")
    v = values[order]
    if weights is None:
        n = len(v)
        if n == 0:
            return 0.0
        pos = alpha * n
        idx = int(np.floor(pos))
        if idx >= n:
            return float(v[-1])
        if abs(pos - idx) < 1e-12 and idx > 0:
            return float((v[idx - 1] + v[idx]) / 2.0)
        return float(v[idx])
    w = weights[order]
    cum = np.cumsum(w) - 0.5 * w
    total = np.sum(w)
    if total <= 0:
        return 0.0
    target = alpha * total
    idx = int(np.searchsorted(cum, target))
    idx = min(idx, len(v) - 1)
    return float(v[idx])
