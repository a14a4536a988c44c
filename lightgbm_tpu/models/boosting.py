"""Boosting variants: GOSS, DART, RF + factory
(reference: src/boosting/boosting.cpp:35 ``Boosting::CreateBoosting``,
goss.hpp:25 ``GOSS``, dart.hpp ``DART``, rf.hpp:25 ``RF``)."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..dataset import Dataset
from ..utils.log import log_warning
from ..utils.random import host_rng
from .gbdt import GBDT, goss_rates, goss_sample


class GOSS(GBDT):
    """Gradient-based One-Side Sampling (reference src/boosting/goss.hpp:
    keep top ``top_rate`` rows by |g*h|, Bernoulli-sample ``other_rate`` of
    the rest and amplify their gradients by (1-a)/b, :103-152; sampling is
    skipped for the first 1/learning_rate iterations, :157).

    The reference samples an exact count with a per-thread RNG; here the
    "rest" rows are sampled i.i.d. Bernoulli — same distribution,
    deterministic per (seed, iteration).  The draw is ONE jitted function
    on the device (``gbdt.goss_sample``: exact threshold, draw and the
    scaling of the gradients in one program, no host copy, so the host
    still runs a tree ahead); the chunked streamed driver and the
    multi-model batcher reach the same function through its host face
    (``gbdt.goss_sample_np``), so all three thin the same rows and stay
    bit-identical to each other.  There is no host random stream."""

    name = "goss"

    def __init__(self, config: Config, train_set: Optional[Dataset],
                 objective=None) -> None:
        super().__init__(config, train_set, objective)
        if config.bagging_freq > 0 and config.bagging_fraction < 1.0:
            log_warning("cannot use bagging in GOSS (ignored)")

    def _prepare_iter_sampling(self, grad, hess):
        cfg = self.config
        rates = goss_rates(cfg, self.iter_)
        if rates is None:
            self._last_sample = None
            return grad, hess, self._all_rows_mask(), self.num_data
        self._last_sample, mask, grad, hess, sampled_rows = goss_sample(
            grad, hess, self.iter_, top_rate=rates[0], other_rate=rates[1],
            bagging_seed=int(cfg.bagging_seed))
        return grad, hess, mask, sampled_rows


class DART(GBDT):
    """Dropouts meet MART (reference src/boosting/dart.hpp: ``DroppingTrees``
    at :97 — weighted drop selection, train-score subtraction, per-iteration
    shrinkage lr/(1+k) — and ``Normalize`` at :158 — dropped trees rescaled
    to weight*k/(k+1)).

    Each tree's unshrunk train/valid predictions are cached on device so
    drop/renormalize score adjustments are O(N) axpy ops instead of tree
    re-walks."""

    name = "dart"
    # DART reads/rescales host trees every iteration (Normalize), so tree
    # deferral buys nothing and would corrupt weights if _normalize ever
    # indexed _models_list directly — opt out explicitly.
    _defer_trees = False

    def __init__(self, config: Config, train_set: Optional[Dataset],
                 objective=None) -> None:
        super().__init__(config, train_set, objective)
        self._base_pred: list = []        # per iteration: raw train pred (N,[K])
        self._valid_base_pred: list = []  # per iteration: list per valid set
        self._weights: list = []          # current weight (includes shrinkage)
        self._sum_weight = 0.0
        self._cur_shrinkage = float(config.learning_rate)
        self._drop_idx: list = []

    def _current_shrinkage(self) -> float:
        return self._cur_shrinkage

    def train_one_iter(self, grad=None, hess=None) -> bool:
        cfg = self.config
        lr = float(cfg.learning_rate)
        rng = host_rng(cfg.drop_seed, self.iter_)
        t = self.iter_
        drop: list = []
        if t > 0 and not (rng.random() < cfg.skip_drop):
            if cfg.uniform_drop:
                p = cfg.drop_rate
                if cfg.max_drop > 0:
                    p = min(p, cfg.max_drop / float(t))
                for i in range(t):
                    if rng.random() < p:
                        drop.append(i)
                        if cfg.max_drop > 0 and len(drop) >= cfg.max_drop:
                            break
            else:
                inv_avg = t / max(self._sum_weight, 1e-12)
                p = cfg.drop_rate
                if cfg.max_drop > 0:
                    p = min(p, cfg.max_drop * inv_avg / max(self._sum_weight,
                                                            1e-12))
                for i in range(t):
                    if rng.random() < p * self._weights[i] * inv_avg:
                        drop.append(i)
                        if cfg.max_drop > 0 and len(drop) >= cfg.max_drop:
                            break
        self._drop_idx = drop
        kd = float(len(drop))
        # remove dropped trees from the TRAIN score (valid handled in
        # normalize, like the reference)
        for d in drop:
            self.score = self.score - self._base_pred[d] * self._weights[d]
        if cfg.xgboost_dart_mode:
            self._cur_shrinkage = lr if not drop else lr / (lr + kd)
        else:
            self._cur_shrinkage = lr / (1.0 + kd)
        res = super().train_one_iter(grad, hess)
        self._normalize(drop)
        return res

    def _record_tree(self, grown, class_id):
        self._valid_deltas_this_tree = []
        n_valid_before = [np.asarray(v).copy() for v in self.valid_scores]
        tree = super()._record_tree(grown, class_id)
        w = self._cur_shrinkage
        base = grown.leaf_value[grown.row_leaf]  # raw, unshrunk
        if self.num_tree_per_iteration == 1:
            pred = base
        else:
            z = jnp.zeros(self.score.shape, jnp.float32)
            pred = z.at[:, class_id].set(base)
        if class_id == 0:
            self._base_pred.append(pred)
            self._weights.append(w)
            self._sum_weight += w
            vb = []
            for vi in range(len(self.valid_sets)):
                delta = jnp.asarray(self.valid_scores[vi]) - jnp.asarray(
                    n_valid_before[vi])
                vb.append(delta / w)
            self._valid_base_pred.append(vb)
        else:
            self._base_pred[-1] = self._base_pred[-1] + pred
            for vi in range(len(self.valid_sets)):
                delta = jnp.asarray(self.valid_scores[vi]) - jnp.asarray(
                    n_valid_before[vi])
                self._valid_base_pred[-1][vi] = \
                    self._valid_base_pred[-1][vi] + delta / w
        return tree

    def _normalize(self, drop_idx) -> None:
        cfg = self.config
        kd = float(len(drop_idx))
        if kd == 0:
            return
        lr = float(cfg.learning_rate)
        factor = kd / (kd + lr) if cfg.xgboost_dart_mode else kd / (kd + 1.0)
        kk = self.num_tree_per_iteration
        for d in drop_idx:
            old_w = self._weights[d]
            new_w = old_w * factor
            self._weights[d] = new_w
            self._sum_weight -= old_w - new_w
            for c in range(kk):
                self.models[d * kk + c].shrink(factor)
            # train score: re-add at the new weight (was fully removed)
            self.score = self.score + self._base_pred[d] * new_w
            # valid score: adjust by the weight delta (was never removed)
            for vi in range(len(self.valid_sets)):
                self.valid_scores[vi] = self.valid_scores[vi] + \
                    self._valid_base_pred[d][vi] * (new_w - old_w)


class RF(GBDT):
    """Random forest mode (reference src/boosting/rf.hpp:25): bagging
    mandatory, no shrinkage, scores are the average of tree outputs and
    gradients are always computed against the averaged score.

    The boost-from-average init score is folded into EVERY tree's leaf
    values (averaging then preserves it, and loaded models predict
    correctly with a plain tree-average)."""

    name = "rf"

    def __init__(self, config: Config, train_set: Optional[Dataset],
                 objective=None) -> None:
        if train_set is not None and \
                not (config.bagging_freq > 0 and config.bagging_fraction < 1.0) \
                and config.feature_fraction >= 1.0:
            raise ValueError("RF mode requires bagging "
                             "(bagging_freq > 0 and bagging_fraction < 1) "
                             "or feature_fraction < 1")
        super().__init__(config, train_set, objective)
        self._tree_sum: Optional[jnp.ndarray] = None
        self._valid_tree_sum: list = []
        self._valid_base: list = []
        if train_set is not None:
            md = self.train_set.metadata
            if md.init_score is not None:
                self._rf_base = jnp.asarray(
                    md.init_score.reshape(self.score.shape), jnp.float32)
            else:
                self._rf_base = jnp.zeros(self.score.shape, jnp.float32)

    def _current_shrinkage(self) -> float:
        return 1.0

    def add_valid(self, valid_set, name):
        super().add_valid(valid_set, name)
        md = valid_set.metadata
        shape = self.valid_scores[-1].shape
        if md.init_score is not None:
            self._valid_base.append(jnp.asarray(md.init_score.reshape(shape),
                                                jnp.float32))
        else:
            self._valid_base.append(jnp.zeros(shape, jnp.float32))
        self._valid_tree_sum.append(None)

    def _record_tree(self, grown, class_id):
        from .gbdt import _grown_to_tree
        tree = _grown_to_tree(grown, 1.0, self.train_set)
        bias = float(self._pending_bias[class_id])
        if abs(bias) > 1e-12:
            tree.add_bias(bias)
        self.models.append(tree)
        k = self.num_tree_per_iteration
        lv = grown.leaf_value + bias
        pred = lv[grown.row_leaf]
        t = self.iter_ + 1
        if self._tree_sum is None:
            self._tree_sum = jnp.zeros(self.score.shape, jnp.float32)
        if k == 1:
            self._tree_sum = self._tree_sum + pred
        else:
            self._tree_sum = self._tree_sum.at[:, class_id].add(pred)
        self.score = self._rf_base + self._tree_sum / t
        for vi, (_, vset) in enumerate(self.valid_sets):
            vbins = vset._device_cache["bins"]
            delta = self._walk(vbins, grown.split_feature, grown.threshold_bin,
                                 grown.nan_bin, grown.cat_member,
                                 grown.decision_type,
                                 grown.left_child, grown.right_child,
                                 jnp.asarray(lv, jnp.float32), grown.num_leaves)
            if self._valid_tree_sum[vi] is None:
                self._valid_tree_sum[vi] = jnp.zeros(
                    self.valid_scores[vi].shape, jnp.float32)
            if k == 1:
                self._valid_tree_sum[vi] = self._valid_tree_sum[vi] + delta
            else:
                self._valid_tree_sum[vi] = \
                    self._valid_tree_sum[vi].at[:, class_id].add(delta)
            self.valid_scores[vi] = self._valid_base[vi] + \
                self._valid_tree_sum[vi] / t
        return tree

    def predict(self, X, raw_score=False, start_iteration=0,
                num_iteration=None, pred_leaf=False, pred_contrib=False,
                **kwargs):
        out = super().predict(X, raw_score=True,
                              start_iteration=start_iteration,
                              num_iteration=num_iteration,
                              pred_leaf=pred_leaf, pred_contrib=pred_contrib)
        if pred_leaf or pred_contrib:
            return out
        k = self.num_tree_per_iteration
        t = max(1, len(self.models) // k)
        out = out / t
        if raw_score or self.objective is None:
            return out
        return np.asarray(self.objective.convert_output(jnp.asarray(out)))


def create_boosting(config: Config, train_set: Optional[Dataset],
                    objective=None) -> GBDT:
    """Factory (reference src/boosting/boosting.cpp:35)."""
    kind = config.boosting
    if kind == "gbdt":
        return GBDT(config, train_set, objective)
    if kind == "goss":
        return GOSS(config, train_set, objective)
    if kind == "dart":
        return DART(config, train_set, objective)
    if kind == "rf":
        return RF(config, train_set, objective)
    raise ValueError(f"Unknown boosting type: {kind}")
