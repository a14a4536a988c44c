"""Booster: user-facing trained-model handle.

Mirrors the reference Python package's Booster
(reference: python-package/lightgbm/basic.py ``Booster`` — train/eval/
predict/save surface; the ctypes C-API indirection collapses because the
boosting driver is in-process).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .config import Config
from .dataset import Dataset
from .models.boosting import create_boosting
from .utils.log import log_warning

__all__ = ["Booster"]


# cells Booster.predict densifies of a sparse input at a time (0.5 GB of float32)
_SPARSE_PREDICT_CELLS = 1 << 27


class Booster:
    """Trained-model handle (reference basic.py Booster; C-side
    src/c_api.cpp:108 Booster wrapper)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None,
                 silent: bool = False) -> None:
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_data_name = "training"

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("train_set must be a Dataset")
            self.config = Config(self.params)
            train_set.construct(self.config)
            self._gbdt = create_boosting(self.config, train_set)
        elif model_file is not None:
            # binary-mode read: a corrupt file with stray invalid utf-8
            # must surface as ModelCorruptError, not UnicodeDecodeError
            with open(model_file, "rb") as fh:
                raw = fh.read()
            try:
                text = raw.decode("utf-8")
            except UnicodeDecodeError as exc:
                from .models.model_text import ModelCorruptError
                raise ModelCorruptError(str(model_file), exc.start,
                                        "not utf-8 text") from exc
            self._load_from_string(text, source=str(model_file))
        elif model_str is not None:
            self._load_from_string(model_str)
        else:
            raise ValueError("Booster needs train_set, model_file or model_str")

    def _load_from_string(self, model_str: str,
                          source: str = "<model string>") -> None:
        from .models.model_text import string_to_model
        self.config = Config(self.params)
        self._gbdt = string_to_model(model_str, self.config, source=source)

    # -- training ------------------------------------------------------------
    def update(self, train_set: Optional[Dataset] = None,
               fobj=None) -> bool:
        """One boosting iteration (reference LGBM_BoosterUpdateOneIter /
        basic.py Booster.update).  ``fobj(preds, train_set) -> (grad, hess)``
        enables custom objectives."""
        if train_set is not None and train_set is not self._gbdt.train_set:
            # the reference skips ResetTrainingData for the identical
            # Dataset (basic.py is_the_same_train_set check) — resetting
            # rebuilds scores over every tree, which would turn a cheap
            # no-op into O(trees x N) per update call
            self.reset_train_data(train_set)
        if fobj is not None:
            preds = np.asarray(self._gbdt.score)
            grad, hess = fobj(preds, self._gbdt.train_set)
            return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))
        return self._gbdt.train_one_iter()

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def reset_train_data(self, train_set: Dataset) -> "Booster":
        """Swap the training dataset under the existing model (reference
        Booster::ResetTrainingData / LGBM_BoosterResetTrainingData):
        trees are kept, scores rebuild on the new rows, and further
        ``update()`` calls continue boosting on them."""
        if not isinstance(train_set, Dataset):
            raise TypeError("train_set must be a Dataset")
        self._gbdt.reset_train_data(train_set)
        return self

    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit the existing tree structures on new data
        (reference basic.py:2976 Booster.refit -> LGBM_BoosterRefit ->
        GBDT::RefitTree): every tree keeps its splits; leaf values become
        ``decay_rate * old + (1 - decay_rate) * new`` where the new value is
        the closed-form output of the leaf's rows in ``data``."""
        if self._gbdt.objective is None:
            raise ValueError("Cannot refit due to null objective function.")
        leaf_preds = self.predict(data, pred_leaf=True, **kwargs)
        new_params = dict(self.params)
        new_params["refit_decay_rate"] = decay_rate
        train_set = Dataset(data, label)
        new_booster = Booster(params=new_params, train_set=train_set)
        new_booster._gbdt.refit_trees(self._gbdt, np.asarray(leaf_preds))
        return new_booster

    @property
    def train_record(self):
        """Telemetry record of this booster's training run
        (:class:`~lightgbm_tpu.telemetry.TrainRecord`): per-tree
        histogram passes, per-phase wall time, trace-time collective
        tallies, XLA compile events, device-memory watermark.  Call
        ``.snapshot()`` for a JSON-ready dict; the same record is
        exported by the serve ``/metrics`` endpoint as the process's
        last training run."""
        return self._gbdt.train_record

    @property
    def current_iteration(self) -> int:
        return self._gbdt.current_iteration

    def num_trees(self) -> int:
        return self._gbdt.num_trees()

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    def num_feature(self) -> int:
        # reference reports the ORIGINAL column count (num_total_features),
        # not the post-trivial-filter inner count
        return self._gbdt.feature_mapping()[1]

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        self._gbdt.add_valid(data, name)
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """reference basic.py reset_parameter -> LGBM_BoosterResetParameter;
        supports learning-rate style schedule changes."""
        self.params.update(params)
        self.config = self.config.update(params)
        self._gbdt.config = self.config
        return self

    # -- evaluation ----------------------------------------------------------
    def eval_train(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        out = self._gbdt.eval_train()
        if feval is not None:
            out = out + self._run_feval(feval, "training",
                                        np.asarray(self._gbdt.score),
                                        self._gbdt.train_set)
        return out

    def eval_valid(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        out = self._gbdt.eval_valid()
        if feval is not None:
            for vi, (vname, vset) in enumerate(self._gbdt.valid_sets):
                out = out + self._run_feval(
                    feval, vname, np.asarray(self._gbdt.valid_scores[vi]), vset)
        return out

    def _run_feval(self, feval, name, score, dset):
        res = feval(score, dset)
        if isinstance(res, tuple):
            res = [res]
        return [(name, r[0], float(r[1]), bool(r[2])) for r in res]

    # -- prediction ----------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: Optional[int] = None,
                pred_early_stop_margin: Optional[float] = None,
                **kwargs) -> np.ndarray:
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else None
        if hasattr(data, "to_numpy"):
            data = data.to_numpy(dtype=np.float64, na_value=np.nan)
        how = dict(raw_score=raw_score, start_iteration=start_iteration,
                   num_iteration=num_iteration, pred_leaf=pred_leaf,
                   pred_contrib=pred_contrib,
                   pred_early_stop=pred_early_stop,
                   pred_early_stop_freq=pred_early_stop_freq,
                   pred_early_stop_margin=pred_early_stop_margin)
        if hasattr(data, "tocsr"):
            # sparse rows are densified a slice at a time: never more than
            # _SPARSE_PREDICT_CELLS values at once (12M x 4,228 whole would
            # be 412 GB of float64), and in their own dtype: the predictor
            # walks float32, a float64 copy of float32 values adds nothing
            data = data.tocsr()
            step = max(1, _SPARSE_PREDICT_CELLS // max(1, data.shape[1]))
            return np.concatenate([
                self._gbdt.predict(data[lo:lo + step].toarray(), **how)
                for lo in range(0, max(data.shape[0], 1), step)], axis=0)
        return self._gbdt.predict(np.asarray(data, dtype=np.float64), **how)

    def to_predictor(self, num_iteration: Optional[int] = None,
                     warmup: bool = False, **kwargs):
        """Serving handle for this model: a
        :class:`~lightgbm_tpu.serve.CompiledPredictor` holding the
        ensemble device-resident with jit-compiled prediction per shape
        bucket (``warmup=True`` compiles every bucket ahead of the first
        request).  See ``lightgbm_tpu.serve`` for the registry /
        micro-batching / HTTP layers above it."""
        from .serve import CompiledPredictor
        pred = CompiledPredictor(self, num_iteration=num_iteration, **kwargs)
        if warmup:
            pred.warmup()
        return pred

    # -- model IO ------------------------------------------------------------
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        return self._gbdt.save_model_to_string(
            start_iteration, -1 if num_iteration is None else num_iteration)

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        # temp + fsync + atomic rename: mid-train snapshots (and any other
        # save racing a crash) can never leave a truncated model file
        from .io_utils import atomic_write_text
        atomic_write_text(filename,
                          self.model_to_string(num_iteration, start_iteration,
                                               importance_type))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> Dict[str, Any]:
        from .models.model_text import model_to_dict
        return model_to_dict(self._gbdt, start_iteration,
                             -1 if num_iteration is None else num_iteration)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        return self._gbdt.feature_importance(importance_type)

    def feature_name(self) -> List[str]:
        # full ORIGINAL column names (reference returns num_total_features
        # names, matching num_feature()/feature_importance() lengths)
        return self._gbdt.feature_mapping()[2]

    # network emulation (reference basic.py:2178 set_network) ---------------
    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120, num_machines: int = 1) -> "Booster":
        """Reference socket-mesh bootstrap.  Here distribution rides the JAX
        device mesh instead: single-host multi-chip needs only
        ``tree_learner='data'`` (+ ``num_devices``); multi-host processes
        must call ``lightgbm_tpu.distributed.init(...)`` before training.
        Raises rather than silently pretending a socket mesh exists."""
        n_machines = (len(machines.split(",")) if isinstance(machines, str)
                      else len(machines)) if machines else num_machines
        if n_machines > 1:
            raise NotImplementedError(
                "set_network(machines=...) maps to the JAX multi-process "
                "runtime here: call lightgbm_tpu.distributed.init(coordinator"
                "_address=..., num_processes=..., process_id=...) in every "
                "process, then train with tree_learner='data'. A socket mesh "
                "is never created, so returning success would be a lie.")
        log_warning("set_network with a single machine is a no-op: set "
                    "tree_learner='data'/'feature'/'voting' and num_devices "
                    "to shard over the local JAX mesh instead")
        return self

    def free_network(self) -> "Booster":
        return self
