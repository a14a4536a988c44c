"""One-program multi-model training: the vmapped batch boosting driver.

M boosters train inside ONE compiled program: per-model state (scores,
gradients, bagging/feature masks, RNG keys, swept hyperparameters) is
stacked along a leading model axis and the single-tree grower — the SAME
factory-built function a standalone ``train()`` uses
(learner/serial.py ``SerialTreeLearner.build_grow_fn``) — is ``jax.vmap``-ed
over it.  The binned dataset, the feature descriptors and the compiled
step are shared across all M models.

Bit-identity contract: model m of a batch is bit-identical to the model a
standalone ``train(variants[m])`` with the same seeds would produce.
This holds because

* the grower's histogram build + split scan are value-deterministic
  under vmap (each model's lane runs the same reduction tree — asserted
  by tests/test_multitrain.py on the partition and wave paths);
* sampling draws are single-sourced (models/gbdt.py
  ``bagging_mask_np`` / ``feature_mask_np`` on the host; the GOSS draw
  is the one jitted ``goss_sample``, reached here through its host face
  ``goss_sample_np``) and keyed per model by the variant's own seeds;
* swept hyperparameters enter the traced program as per-model scalars
  that flow through the exact arithmetic the constant-folded standalone
  program runs (ops/split.py ``TRACEABLE_PARAMS``);
* the per-iteration dispatch BOUNDARIES mirror the standalone loop
  (eager gradients, one jitted grower program, an eager
  ``leaf_value * shrinkage`` multiply, the jitted gather+add score
  update, the jitted valid-set walk plus an eager add).  Fusing them
  into one program is NOT value-safe: XLA contracts the multiply into
  the score add as a single-rounding FMA — ``optimization_barrier``
  does not stop it on the CPU backend — and drifts 1 ulp off the
  standalone trajectory.

Boosting/objective variants ride the same axis (the PR-20 lift):

* **GOSS** (arXiv:1806.11248) — per-lane top-a%/random-b% draws come
  from the one sampler (``gbdt.goss_sample``, jitted; called per lane
  through its host face ``gbdt.goss_sample_np``, no Philox stream) on
  the already-eager (M, N) gradient matrix; the amplified small-gradient
  multipliers hit the stacked gradients in one eager elementwise
  multiply and the 0/1 survivorship folds into the per-lane grower
  mask, so every lane's inputs equal its standalone counterpart's.
* **DART** — per-lane drop sets are ``utils/random.host_rng`` host
  bookkeeping in ``_ModelState``; each iteration's raw per-tree
  predictions are cached as ONE stacked (L, N) gather, and drop
  subtraction / re-add / valid renormalization are batched
  ``jnp.where``-masked axpys over all lanes, so lanes never
  desynchronize the dispatch boundaries.  Tree shrink-factor replays
  happen at finalize in standalone chronological order.
* **multiclass** — an (M, K) lane grid flattened to L = M*K device
  lanes: softmax/OVA gradients are vmapped per model on the (N, K)
  score view, every class tree of an iteration grows in the same
  vmapped program (the standalone class loop's trees are mutually
  independent within an iteration), and extraction interleaves class
  trees exactly like the standalone loop.
* **ranking** — lambdarank/rank-xendcg gradients vectorize across lanes
  over the one shared padded query-segment layout (per-lane scores in);
  ``train_set.metadata.group`` is no longer a reject.

The per-iteration host work is only mask refreshes, DART/GOSS draws and
metric evaluation; the heavy lifting (histogram build + split scan for
all M*K lanes) is the single vmapped grower program per iteration.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.contracts import memory_budget
from ..basic import Booster
from ..callback import CallbackEnv, EarlyStopException, early_stopping
from ..config import Config
from ..dataset import Dataset, Metadata
from ..learner.serial import GrownTree, SerialTreeLearner
from ..metric import create_metrics
from ..models.gbdt import (EPSILON, _grown_to_tree, _mappers_equal,
                           _update_score_by_leaf, bagging_mask_np,
                           feature_mask_np, goss_sample_np, make_walk_fn)
from ..objective import create_objective
from ..resilience.checkpoint import reject_checkpointing
from ..resilience.faults import faults
from ..telemetry.metrics import default_registry
from ..telemetry.train_record import TrainRecord, set_last_train_record
from ..utils.random import host_rng
from .variants import TRACED_SWEEP

__all__ = ["MultiTrainError", "BatchTrainer", "batch_reject_reason"]


def multitrain_hbm_bytes(ctx):
    """Per-device HBM curve of the stacked vmapped grower program
    (lint-mem enforced): every wave-grower working buffer except the
    shared bin matrix picks up a leading lane axis of L = models *
    classes (the multiclass (M, K) grid flattens onto the same vmap
    axis), so the footprint is ~L x the standalone curve — the reason
    tpu_multitrain_batch caps a structure group at 256 models and the
    lane axis shard_map-shards across devices when L % ndev == 0 (each
    device then holds L/ndev lanes)."""
    from ..learner.wave import wave_grow_hbm_bytes
    m = max(1, int(ctx.get("models", 1)))
    k = max(1, int(ctx.get("classes", 1)))
    ndev = max(1, int(ctx.get("model_shards", 1)))
    lanes = -(-(m * k) // ndev)
    per_model = wave_grow_hbm_bytes(ctx)
    # 1.15: vmap stacks a few lane-wide temporaries the standalone
    # program frees between dispatches (measured at the lint-mem
    # geometry)
    return int(1.15 * lanes * per_model)


memory_budget("multitrain/stacked_state", ("multitrain", "multitrain_mc"),
              multitrain_hbm_bytes,
              note="M*K/ndev lanes x the wave-grower curve (shared bins)")


class MultiTrainError(ValueError):
    """The configuration cannot train on the vmapped model axis."""


# objectives the model axis cannot express: "none" means a custom fobj
# whose host callback cannot stack
_UNSUPPORTED_OBJECTIVES = ("none",)


def batch_reject_reason(cfg: Config, train_set: Dataset) -> Optional[str]:
    """Why this config cannot ride the vmapped model axis (None = it can).

    The excluded features either keep cross-tree host state whose score
    effects the batch cannot replay (RF's averaged scores, CEGB
    used-sets, linear-leaf refits, L1-style leaf renewal), or change the
    traced program per model (distributed learners).  GOSS, DART,
    multiclass and ranking all batch (PR 20): their host state stacks in
    ``_ModelState`` and their score adjustments are lane-masked device
    ops."""
    if cfg.boosting not in ("gbdt", "goss", "dart", ""):
        return f"boosting={cfg.boosting} (averaged-score training)"
    if cfg.objective in _UNSUPPORTED_OBJECTIVES:
        return f"objective={cfg.objective}"
    if cfg.tree_learner not in ("serial", ""):
        return f"tree_learner={cfg.tree_learner} (mesh collectives)"
    if cfg.linear_tree:
        return "linear_tree (host-side leaf fits)"
    if (cfg.cegb_penalty_split > 0 or cfg.cegb_penalty_feature_coupled or
            cfg.cegb_penalty_feature_lazy):
        return "CEGB penalties (cross-tree used-feature state)"
    if getattr(train_set, "distributed_rows", False):
        return "pre_partition-ed multi-process dataset"
    return None


def _objective_reject_reason(objective) -> Optional[str]:
    if objective is None:
        return "custom objective (fobj)"
    if getattr(objective, "is_renew_tree_output", False):
        return (f"objective {type(objective).__name__} renews leaf values "
                "host-side per tree")
    return None


def _subset_metadata(md: Metadata, rows: np.ndarray,
                     mask_vals: Optional[np.ndarray] = None) -> Metadata:
    """Metadata restricted to ``rows`` (the standalone counterpart's
    ``Dataset.subset`` view).  Fractional mask values fold into the
    weights so a soft-masked model's boost_from_average matches its
    effective objective."""
    sub = Metadata()
    if md.label is not None:
        sub.set_label(np.asarray(md.label)[rows])
    w = None if md.weight is None else np.asarray(md.weight)[rows]
    if mask_vals is not None and not np.all(mask_vals == 1.0):
        w = mask_vals if w is None else w * mask_vals
    if w is not None:
        sub.set_weight(w)
    if md.init_score is not None:
        sub.set_init_score(np.asarray(md.init_score)[rows])
    return sub


class _ModelState:
    """Host bookkeeping of one model lane group (all K class lanes)."""

    __slots__ = ("cfg", "params", "rows", "mask_vals", "bias", "active",
                 "kept_iters", "best_iteration", "best_score", "stopper",
                 "history", "metrics_per_valid", "stop_reason",
                 # DART host state (per model, mirrors models/boosting.py)
                 "weights", "sum_weight", "cur_shrinkage", "tree_shrink",
                 "tree_factors")

    def __init__(self, cfg: Config, params: Dict[str, Any]) -> None:
        self.cfg = cfg
        self.params = params
        self.rows: Optional[np.ndarray] = None
        self.mask_vals: Optional[np.ndarray] = None
        self.bias: Optional[np.ndarray] = None   # (K,) per-class init bias
        self.active = True
        self.kept_iters = 0
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self.stopper = None
        self.history: Dict[str, Dict[str, List[float]]] = {}
        self.metrics_per_valid: List[list] = []
        self.stop_reason = ""
        self.weights: List[float] = []       # DART per-tree current weight
        self.sum_weight = 0.0
        self.cur_shrinkage = float(cfg.learning_rate)
        self.tree_shrink: List[float] = []   # shrinkage at creation time
        self.tree_factors: List[List[float]] = []  # normalize replays


class BatchTrainer:
    """Trains one same-structure group of M variants in one program.

    Drivers (``train_many``, the CV fast path, the sweep) construct it,
    call :meth:`run` or drive :meth:`step_once` themselves, then
    :meth:`finalize` to extract per-model standalone ``Booster``s.

    Multiclass objectives put K = num_class lanes per model on the vmap
    axis (L = M*K device lanes, class-major within a model, matching the
    standalone per-iteration class loop); all host bookkeeping stays at
    model granularity and expands to lanes on upload."""

    def __init__(self, variant_params: List[Dict[str, Any]],
                 train_set: Dataset,
                 sample_rows: Optional[List[Optional[np.ndarray]]] = None,
                 sample_masks: Optional[np.ndarray] = None,
                 valid_sets: Optional[List[Dataset]] = None,
                 valid_names: Optional[List[str]] = None,
                 force_traced: bool = False) -> None:
        self.M = len(variant_params)
        if self.M == 0:
            raise MultiTrainError("empty variant batch")
        self.params = [dict(p) for p in variant_params]
        self.cfgs = [Config(p) for p in self.params]
        cfg = self.cfgs[0]
        self.cfg = cfg
        reject_checkpointing(cfg, "train_many")
        train_set.construct(cfg)
        reason = batch_reject_reason(cfg, train_set)
        if reason:
            raise MultiTrainError(reason)
        self.train_set = train_set
        self.n = train_set.num_data()
        self.num_features = train_set.num_feature()
        self.boosting = cfg.boosting or "gbdt"   # structural: whole batch
        self._goss = self.boosting == "goss"
        self._dart = self.boosting == "dart"

        # the shared objective: gradients are per-row (elementwise, or
        # row-local softmax / query-local lambdarank), so one instance
        # initialized on the FULL metadata serves every model (per-model
        # row masks never reach gradient VALUES)
        self.objective = (create_objective(cfg.objective, cfg)
                          if cfg.objective != "none" else None)
        reason = _objective_reject_reason(self.objective)
        if reason:
            raise MultiTrainError(reason)
        self.objective.init(train_set.metadata, self.n)
        self.K = int(self.objective.num_model_per_iteration)
        self.L = self.M * self.K
        self._ranking = train_set.metadata.group is not None
        if cfg.objective == "rank_xendcg" and \
                len({int(c.seed) for c in self.cfgs}) > 1:
            raise MultiTrainError(
                "rank_xendcg seed sweep (the sampled-lambda stream is "
                "shared across lanes)")

        # the learner: same selection path as GBDT._init_train
        from ..binning import MissingType
        mappers = [train_set.bin_mappers[j] for j in train_set.used_feature_map]
        self.max_bins = int(max(m.num_bin for m in mappers))
        num_bins = np.array([m.num_bin for m in mappers], np.int32)
        is_cat = np.array([m.is_categorical for m in mappers], bool)
        has_nan = np.array(
            [m.missing_type == MissingType.NAN for m in mappers], bool)
        from ..models.gbdt import GBDT
        shim = GBDT.__new__(GBDT)
        shim.config = cfg
        shim.train_set = train_set
        shim.num_features = self.num_features
        shim.max_bins = self.max_bins
        monotone = GBDT._inner_monotone(shim)
        self.learner = SerialTreeLearner(
            cfg, self.num_features, self.max_bins, num_bins, is_cat,
            has_nan, monotone, GBDT._parse_forced_splits(shim),
            efb=train_set.efb,
            interaction_groups=GBDT._parse_interaction_constraints(shim),
            feature_contri=GBDT._inner_contri(shim),
            cegb_lazy=(),
            # a lane's folds and its own bagging reach the grower as
            # masks with zeros, whatever the first lane's Config says
            sampled=(sample_rows is not None or sample_masks is not None
                     or any(c.samples_rows for c in self.cfgs)))
        if self.learner.grow_mode == "masked":
            raise MultiTrainError(
                "pool-less (masked) grower: histogram pool exceeds budget")
        # The pallas histogram kernels batch on the model axis through
        # jax's pallas_call batching rule (the vmap axis becomes a
        # leading grid dimension), so batched training rides the SAME
        # fast kernels a standalone train() uses — per-lane bit-identity
        # vs standalone is asserted by tests/test_multitrain.py with the
        # interpret-mode kernels.  Only the row-padding contract differs:
        # _build_step pads the batch to the kernel row block.

        # per-model lanes
        self.states = [_ModelState(c, p)
                       for c, p in zip(self.cfgs, self.params)]
        if sample_rows is not None:
            for st, rows in zip(self.states, sample_rows):
                if rows is not None:
                    st.rows = np.asarray(rows, np.int64)
        if sample_masks is not None:
            sample_masks = np.asarray(sample_masks, np.float32)
            if sample_masks.shape != (self.M, self.n):
                raise MultiTrainError(
                    f"sample_masks shape {sample_masks.shape} != "
                    f"({self.M}, {self.n})")
            for m, st in enumerate(self.states):
                nz = np.nonzero(sample_masks[m] > 0)[0]
                st.rows = nz
                st.mask_vals = sample_masks[m][nz]
        any_rows = any(st.rows is not None for st in self.states)
        if any_rows and cfg.is_unbalance and \
                cfg.objective in ("binary", "multiclassova"):
            # the shared objective derives is_unbalance's label_weight
            # from the FULL dataset's pos/neg counts; a fold/cohort
            # model's standalone counterpart derives it from ITS rows —
            # masked gradients would silently weight wrong
            raise MultiTrainError(
                "is_unbalance with per-model sample masks (label_weight "
                "depends on the fold's own pos/neg counts)")
        if any_rows and self._ranking:
            # a fold's standalone counterpart re-segments ITS rows into
            # queries; the shared padded segment layout spans the full
            # dataset and cannot express per-lane query subsets
            raise MultiTrainError(
                "ranking objectives with per-model sample masks (query "
                "segments derive from the full dataset)")

        # swept hyperparameters -> traced (M, S) matrix; fields equal
        # across the batch stay static (max constant folding)
        self.sweep_fields = tuple(
            f for f in TRACED_SWEEP
            if force_traced or len({float(getattr(c, f))
                                    for c in self.cfgs}) > 1)
        self.sweep = np.asarray(
            [[np.float32(getattr(c, f)) for f in self.sweep_fields]
             for c in self.cfgs], np.float32).reshape(self.M,
                                                      len(self.sweep_fields))
        self.lr = np.asarray([np.float32(c.learning_rate)
                              for c in self.cfgs], np.float32)

        self._init_scores()
        self._init_valid(valid_sets or [], valid_names or [])
        self._init_keys()
        self._build_step()

        self._grown: List[GrownTree] = []       # stacked per-iteration
        self._leaves: List[Any] = []            # device (L,) per iteration
        self._dart_base: List[jnp.ndarray] = []  # per iter: raw (L, N) pred
        self._dart_vb: List[List[jnp.ndarray]] = []  # per iter, per valid
        self._steps = 0
        self.record = TrainRecord(meta={
            "boosting": self.boosting, "objective": str(cfg.objective),
            "tree_learner": "serial",
            "multitrain_models": self.M,
            "multitrain_classes": self.K,
            "num_leaves": int(cfg.num_leaves),
            "num_data": int(self.n),
            "num_features": int(self.num_features),
        })
        set_last_train_record(self.record)
        reg = default_registry()
        reg.counter("multitrain_batches_total",
                    "vmapped train_many batches started").inc()
        reg.counter("multitrain_models_total",
                    "models trained on the vmapped model axis").inc(self.M)

    # -- lane helpers --------------------------------------------------------
    def _lanes(self, arr: np.ndarray) -> np.ndarray:
        """(M, ...) host array -> (L, ...): repeat each model's row K times
        (class-major lane order, lane = m*K + c)."""
        return arr if self.K == 1 else np.repeat(arr, self.K, axis=0)

    # -- setup ---------------------------------------------------------------
    def _init_scores(self) -> None:
        md = self.train_set.metadata
        K = self.K
        score0 = np.zeros((self.L, self.n), np.float32)
        for m, st in enumerate(self.states):
            st.bias = np.zeros(K)
            if md.init_score is not None:
                init = md.init_score.reshape(self.n, K) if K > 1 else \
                    md.init_score.reshape(self.n)
                for c in range(K):
                    col = init[:, c] if K > 1 else init
                    score0[m * K + c] += col.astype(np.float32)
            elif st.cfg.boost_from_average:
                if st.rows is None:
                    obj = self.objective
                else:
                    # fold/cohort models: the standalone counterpart
                    # computes its average over ITS rows only
                    obj = create_objective(st.cfg.objective, st.cfg)
                    obj.init(_subset_metadata(md, st.rows, st.mask_vals),
                             len(st.rows))
                for c in range(K):
                    st.bias[c] = obj.boost_from_score(c)
                    score0[m * K + c] += np.float32(st.bias[c])
        self.score = jnp.asarray(score0)

    def _init_valid(self, valid_sets: List[Dataset],
                    valid_names: List[str]) -> None:
        self.valid_sets: List[Tuple[str, Dataset]] = []
        self.vbins: List[jnp.ndarray] = []
        K = self.K
        vscores = []
        for i, vs in enumerate(valid_sets):
            if vs is self.train_set:
                raise MultiTrainError(
                    "valid_sets containing the train set (training "
                    "metrics) is not batched; drop it or use train()")
            name = (valid_names[i] if i < len(valid_names)
                    else f"valid_{i}")
            if not vs.constructed and \
                    getattr(vs, "reference", None) is not self.train_set:
                vs.reference = self.train_set
            vs.construct(self.cfg)
            if vs.bin_mappers is not self.train_set.bin_mappers and \
                    not _mappers_equal(vs.bin_mappers,
                                       self.train_set.bin_mappers):
                raise ValueError(
                    "cannot add validation data: it was constructed "
                    "without reference to the training Dataset")
            nv = vs.num_data()
            v0 = np.zeros((self.L, nv), np.float32)
            for m, st in enumerate(self.states):
                if vs.metadata.init_score is not None:
                    init = vs.metadata.init_score.reshape(nv, K) if K > 1 \
                        else vs.metadata.init_score.reshape(nv)
                    for c in range(K):
                        col = init[:, c] if K > 1 else init
                        v0[m * K + c] += col.astype(np.float32)
                elif st.cfg.boost_from_average:
                    for c in range(K):
                        v0[m * K + c] += np.float32(st.bias[c])
            if "bins" not in vs._device_cache:
                vs._device_cache["bins"] = jnp.asarray(vs.X_binned)
            self.valid_sets.append((name, vs))
            self.vbins.append(vs._device_cache["bins"])
            vscores.append(jnp.asarray(v0))
            for st in self.states:
                metrics = create_metrics(st.cfg)
                for mt in metrics:
                    mt.init(vs.metadata, nv)
                st.metrics_per_valid.append(metrics)
        self.vscores = tuple(vscores)

    def _init_keys(self) -> None:
        lrn = self.learner
        self._need_quant_key = bool(lrn.quantized)
        sp = lrn.split_params
        self._need_node_key = (sp.feature_fraction_bynode < 1.0 or
                               sp.extra_trees)
        K = self.K
        if self._need_quant_key:
            self._quant_base = jnp.stack(
                [jax.random.PRNGKey(int(st.cfg.seed))
                 for st in self.states for _ in range(K)])
        if self._need_node_key:
            self._node_base = jnp.stack([jnp.stack([
                jax.random.PRNGKey(int(st.cfg.feature_fraction_seed)),
                jax.random.PRNGKey(int(st.cfg.extra_seed))])
                for st in self.states for _ in range(K)])
        # per-lane fold values: the standalone key stream folds with
        # it = iter_ * K + class_id (gbdt.py train_one_iter), so each
        # class lane folds its own value
        self._class_of_lane = np.tile(np.arange(K, dtype=np.int64), self.M)
        self._fold_one = jax.jit(jax.vmap(jax.random.fold_in,
                                          in_axes=(0, 0)))
        self._fold_two = jax.jit(jax.vmap(jax.vmap(jax.random.fold_in,
                                                   in_axes=(0, None)),
                                          in_axes=(0, 0)))

    def _fold_vals(self, it: int) -> jnp.ndarray:
        return jnp.asarray(it * self.K + self._class_of_lane)

    def _build_step(self) -> None:
        lrn = self.learner
        wave = lrn.grow_mode == "wave"
        # the pallas kernels' padded-row layout (pad_rows): the binned
        # matrix is laid out ONCE here, as the standalone learner lays it
        # out; per-model gradient/mask lanes pad inside the vmapped
        # grower and row_leaf trims back to N
        from ..learner.serial import feature_major_bins
        from ..ops.histogram_pallas import pad_rows
        self._row_pad = (pad_rows(self.n) if lrn.pallas else self.n) - self.n
        if wave and lrn.pack4:
            # the Dataset caches the packed feature-major layout (half
            # the bytes), so repeated BatchTrainers (cv folds, sweeps)
            # share it
            self._X_arg = self.train_set.device_bins_packed4()
        else:
            X_dev = jnp.asarray(self.train_set.X_binned)
            if wave:  # not lrn.bind(): it would keep X_dev alive too
                self._X_arg = feature_major_bins(X_dev, self.n + self._row_pad)
            else:
                self._X_arg = jnp.pad(X_dev, ((0, self._row_pad), (0, 0))) \
                    if self._row_pad else X_dev

        base_sp = lrn.split_params
        sweep_fields = self.sweep_fields
        efb_args = lrn._efb_args
        num_bins, is_cat, has_nan = lrn.num_bins, lrn.is_cat, lrn.has_nan
        monotone = lrn.monotone
        F = self.num_features
        need_nk = self._need_node_key
        objective = self.objective
        walk_fn = make_walk_fn(
            None if self.train_set.efb is None else (
                None, jnp.asarray(self.train_set.efb.f_bundle),
                jnp.asarray(self.train_set.efb.f_offset),
                jnp.asarray(self.train_set.efb.f_default),
                jnp.asarray(self.train_set.efb.f_nbins),
                jnp.asarray(self.train_set.efb.f_single)),
            not bool(np.any(np.asarray(lrn.is_cat))))

        row_pad = self._row_pad
        lrn_n = self.n

        def one_grow(X_arg, g, h, mk, fmask, sweep, qkey, nkey):
            sp = base_sp
            if sweep_fields:
                sp = sp._replace(**{f: sweep[i]
                                    for i, f in enumerate(sweep_fields)})
            grow = lrn.build_grow_fn(split_params=sp, jit=False)
            cegb0 = jnp.zeros((F,), jnp.float32)
            if row_pad:
                # pallas row-block padding: padded rows carry mask 0 and
                # contribute nothing (the standalone learner pads the
                # same way in SerialTreeLearner.train)
                g = jnp.pad(g, (0, row_pad))
                h = jnp.pad(h, (0, row_pad))
                mk = jnp.pad(mk, (0, row_pad))
            if wave:
                kw = {k: v for k, v in (("quant_key", qkey),
                                        ("node_key", nkey))
                      if k in lrn._key_names}
                grown = grow(X_arg, g, h, mk, num_bins, is_cat, has_nan,
                             monotone, cegb0, efb_args, fmask, **kw)
            else:
                nk = nkey if need_nk else jnp.zeros((2, 2), jnp.uint32)
                grown = grow(X_arg, g, h, mk, num_bins, is_cat, has_nan,
                             monotone, cegb0, nk, efb_args, fmask)
            if row_pad:
                grown = grown._replace(row_leaf=grown.row_leaf[:lrn_n])
            return grown

        # dispatch boundaries mirror the standalone loop (see module
        # docstring): gradients stay EAGER vmap (elementwise primitives
        # batch with the same per-op rounding the standalone's eager
        # get_gradients dispatches), the grower is ONE jitted program,
        # the score/valid updates ride the standalone's own jitted
        # helpers under eager vmap
        M, K, L, n = self.M, self.K, self.L, self.n
        base_grad = jax.vmap(objective.get_gradients)
        if K == 1:
            self._vm_grad = base_grad
        else:
            # the standalone multiclass objective sees an (N, K) score;
            # lanes are class-major, so the (L, N) state reshapes to the
            # per-model (N, K) view, gradients vmap per MODEL, and the
            # result flattens back — pure layout moves, no arithmetic
            def _vm_grad_mc(score_lanes):
                sc = jnp.swapaxes(score_lanes.reshape(M, K, n), 1, 2)
                g, h = base_grad(sc)
                return (jnp.swapaxes(g, 1, 2).reshape(L, n),
                        jnp.swapaxes(h, 1, 2).reshape(L, n))
            self._vm_grad = _vm_grad_mc
        vm_grow = jax.vmap(one_grow, in_axes=(None, 0, 0, 0, 0, 0, 0, 0))
        # lane-axis sharding: shard_map the vmapped grower over the
        # GLOBAL device mesh so each device grows L/k lanes concurrently
        # (per-device model lanes; multi-host pods shard the lane axis
        # across every host's devices).  Per-lane values are identical
        # either way (a vmap lane's arithmetic is batch-width
        # independent — the bit-identity suite pins this), so sharding
        # is purely a throughput choice.
        ndev = jax.device_count()
        self._shard = (bool(self.cfg.tpu_multitrain_shard) and ndev > 1
                       and self.L >= ndev and self.L % ndev == 0)
        if self._shard:
            from jax.sharding import PartitionSpec as P
            from ..parallel.mesh import get_mesh
            self._ndev = ndev
            mesh = get_mesh(ndev, "models")
            ax = mesh.axis_names[0]
            self._vm_grow = jax.jit(jax.shard_map(
                vm_grow, mesh=mesh,
                in_specs=(P(),) + (P(ax),) * 7,
                out_specs=P(ax), check_vma=False))
        else:
            self._vm_grow = jax.jit(vm_grow)
        self._vm_walk = jax.vmap(walk_fn,
                                 in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0))
        self._vm_upd = jax.vmap(_update_score_by_leaf,
                                in_axes=(0, 0, 0, None))
        # raw per-tree train predictions for DART's drop bookkeeping:
        # one stacked eager gather, the vmap of the standalone's own
        # `leaf_value[row_leaf]`
        self._vm_base_pred = jax.vmap(lambda lv, rl: lv[rl])
        self._lr_dev = jnp.asarray(self._lanes(self.lr))
        # per-iteration shrinkage lanes: DART replaces this every
        # iteration (lr/(1+k_dropped) per model); others keep lr
        self._shrink_dev = self._lr_dev
        self._sweep_dev = jnp.asarray(self._sweep_lanes())

    def _sweep_lanes(self) -> np.ndarray:
        return self._lanes(self.sweep) if self.sweep.size else \
            np.zeros((self.L, 0), np.float32)

    # -- per-iteration host inputs ------------------------------------------
    def _masks_for_iter(self, it: int) -> Optional[np.ndarray]:
        """(M, N) f32 training-row BASE masks for this iteration, or None
        when unchanged from the previous one (device array reused).  The
        bag only moves at bagging-block boundaries (bagging_mask_np is a
        pure function of the block), so off-boundary iterations skip the
        host sampling AND the host->device transfer entirely.  GOSS lanes
        never bag (the standalone GOSS overrides sampling entirely); their
        base mask is the static rows indicator and the per-iteration GOSS
        survivorship multiplies on top in step_once."""
        def _bagged(st):
            if self._goss:
                return False
            return st.cfg.bagging_active
        if it > 0 and not any(
                _bagged(st) and it % max(1, int(st.cfg.bagging_freq)) == 0
                for st in self.states):
            return None
        label = None
        if self.cfg.objective == "binary" and \
                self.train_set.metadata.label is not None:
            label = np.asarray(self.train_set.metadata.label)
        rows_out = []
        for st in self.states:
            base = None if self._goss else bagging_mask_np(
                st.cfg, self.n, it, label=label, rows=st.rows)
            if base is None:
                if st.rows is not None:
                    base = np.zeros(self.n, np.float32)
                    base[st.rows] = 1.0
                else:
                    base = np.ones(self.n, np.float32)
            if st.mask_vals is not None and st.rows is not None:
                sub = base[st.rows] * st.mask_vals
                base = np.zeros(self.n, np.float32)
                base[st.rows] = sub
            rows_out.append(base)
        return np.stack(rows_out)

    def _fmask_for_iter(self, it: int) -> Optional[np.ndarray]:
        any_ff = any(st.cfg.feature_fraction < 1.0 for st in self.states)
        if not any_ff:
            return None if it > 0 else np.ones((self.M, self.num_features),
                                               bool)
        out = np.ones((self.M, self.num_features), bool)
        for m, st in enumerate(self.states):
            fm = feature_mask_np(st.cfg, self.num_features, it)
            if fm is not None:
                out[m] = fm
        return out

    # -- GOSS (per-lane draws over the eager gradient matrix) ---------------
    def _apply_goss(self, it: int, grad, hess):
        """The shared GOSS draw per lane: multiplies the amplified
        small-gradient weights into the stacked gradients (one eager
        elementwise multiply — warmup/inactive lanes multiply by 1.0,
        which is bit-exact) and records the 0/1 survivorship per model
        for the grower mask."""
        mult = None
        gmask = None
        K = self.K
        # one host pull shared across lanes
        gnp = np.asarray(grad)
        hnp = np.asarray(hess)
        for m, st in enumerate(self.states):
            if not st.active:
                continue
            if K == 1:
                gm = goss_sample_np(st.cfg, gnp[m], hnp[m], it, rows=st.rows)
            else:
                g2 = gnp[m * K:(m + 1) * K].T   # (N, K) per-model view
                h2 = hnp[m * K:(m + 1) * K].T
                gm = goss_sample_np(st.cfg, g2, h2, it, rows=st.rows)
            if gm is None:
                continue
            if mult is None:
                mult = np.ones((self.L, self.n), np.float32)
                gmask = np.ones((self.M, self.n), np.float32)
            mask_m, mult_m = gm
            gmask[m] = mask_m
            for c in range(K):
                mult[m * K + c] = mult_m
        if mult is None:
            self._goss_mask = None
            return grad, hess
        self._goss_mask = gmask
        mdev = jnp.asarray(mult)
        return grad * mdev, hess * mdev

    # -- DART (host drop bookkeeping + lane-masked device axpys) -------------
    def _dart_pre(self, it: int) -> Dict[int, List[int]]:
        """Per-model drop draws (the standalone DART.train_one_iter loop,
        models/boosting.py) + batched dropped-tree score subtraction.
        Sets the per-iteration shrinkage lanes."""
        drops: Dict[int, List[int]] = {}
        shrink = np.empty(self.M, np.float32)
        for m, st in enumerate(self.states):
            cfg = st.cfg
            lr = float(cfg.learning_rate)
            if not st.active:
                st.cur_shrinkage = lr
                shrink[m] = np.float32(lr)
                continue
            rng = host_rng(cfg.drop_seed, it)
            t = it
            drop: List[int] = []
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
                    inv_avg = t / max(st.sum_weight, 1e-12)
                    p = cfg.drop_rate
                    if cfg.max_drop > 0:
                        p = min(p, cfg.max_drop * inv_avg /
                                max(st.sum_weight, 1e-12))
                    for i in range(t):
                        if rng.random() < p * st.weights[i] * inv_avg:
                            drop.append(i)
                            if cfg.max_drop > 0 and len(drop) >= cfg.max_drop:
                                break
            if drop:
                drops[m] = drop
            kd = float(len(drop))
            if cfg.xgboost_dart_mode:
                st.cur_shrinkage = lr if not drop else lr / (lr + kd)
            else:
                st.cur_shrinkage = lr / (1.0 + kd)
            shrink[m] = np.float32(st.cur_shrinkage)
        self._shrink_dev = jnp.asarray(self._lanes(shrink))
        # remove dropped trees from the TRAIN score (valid handled in
        # normalize, like the reference): one where-masked axpy per
        # distinct dropped tree index, all lanes in a shared dispatch
        for d in sorted({i for dl in drops.values() for i in dl}):
            wv = np.zeros(self.M, np.float32)
            sel = np.zeros(self.M, bool)
            for m, dl in drops.items():
                if d in dl:
                    wv[m] = np.float32(self.states[m].weights[d])
                    sel[m] = True
            sl = jnp.asarray(self._lanes(sel))
            wl = jnp.asarray(self._lanes(wv))
            self.score = jnp.where(
                sl[:, None],
                self.score - self._dart_base[d] * wl[:, None], self.score)
        return drops

    def _dart_normalize(self, drops: Dict[int, List[int]]) -> None:
        """The standalone DART._normalize: dropped trees rescale to
        weight*k/(k+1), the train score re-adds them at the new weight and
        valid scores adjust by the weight delta — batched as lane-masked
        axpys.  Tree shrink factors are recorded per model for the
        finalize-time replay (the standalone shrinks host trees in
        place)."""
        if not drops:
            return
        new_w = {}
        delta_w = {}
        for m, dl in drops.items():
            st = self.states[m]
            cfg = st.cfg
            kd = float(len(dl))
            lr = float(cfg.learning_rate)
            factor = kd / (kd + lr) if cfg.xgboost_dart_mode else \
                kd / (kd + 1.0)
            for d in dl:
                old = st.weights[d]
                new = old * factor
                st.weights[d] = new
                st.sum_weight -= old - new
                st.tree_factors[d].append(factor)
                new_w[(m, d)] = new
                delta_w[(m, d)] = new - old
        for d in sorted({i for dl in drops.values() for i in dl}):
            nw = np.zeros(self.M, np.float32)
            dw = np.zeros(self.M, np.float32)
            sel = np.zeros(self.M, bool)
            for m, dl in drops.items():
                if d in dl:
                    nw[m] = np.float32(new_w[(m, d)])
                    dw[m] = np.float32(delta_w[(m, d)])
                    sel[m] = True
            sl = jnp.asarray(self._lanes(sel))
            nwl = jnp.asarray(self._lanes(nw))
            self.score = jnp.where(
                sl[:, None],
                self.score + self._dart_base[d] * nwl[:, None], self.score)
            if self.vscores:
                dwl = jnp.asarray(self._lanes(dw))
                self.vscores = tuple(
                    jnp.where(sl[:, None],
                              vs + self._dart_vb[d][vi] * dwl[:, None], vs)
                    for vi, vs in enumerate(self.vscores))

    def step_once(self, it: int) -> None:
        faults.check_train_iter(it)
        masks = self._masks_for_iter(it)
        if masks is not None:
            self._base_masks_np = masks
            self._mask_dev = jnp.asarray(self._lanes(masks))
            if self._goss:
                self._base_mask_dev = self._mask_dev
        fmask = self._fmask_for_iter(it)
        if fmask is not None:
            self._fmask_dev = jnp.asarray(self._lanes(fmask))
        drops = self._dart_pre(it) if self._dart else None
        qk = (self._fold_one(self._quant_base, self._fold_vals(it))
              if self._need_quant_key else self._dummy_qk())
        nk = (self._fold_two(self._node_base, self._fold_vals(it))
              if self._need_node_key else self._dummy_nk())
        with self.record.phase("gradients"):
            grad, hess = self._vm_grad(self.score)
            if self._goss:
                grad, hess = self._apply_goss(it, grad, hess)
                if self._goss_mask is not None:
                    self._mask_dev = jnp.asarray(self._lanes(
                        self._base_masks_np * self._goss_mask))
                else:
                    self._mask_dev = self._base_mask_dev
        with self.record.phase("grow"):
            # sharded or not, one (L, ...) call: the shard_map lane
            # split happens on-device (no host (k, L/k) reshape)
            grown = self._vm_grow(self._X_arg, grad, hess,
                                  self._mask_dev, self._fmask_dev,
                                  self._sweep_dev, qk, nk)
        if self._dart:
            # raw (unshrunk) per-tree train predictions, one stacked
            # gather — the standalone's `leaf_value[row_leaf]`
            self._dart_base.append(
                self._vm_base_pred(grown.leaf_value, grown.row_leaf))
            for st in self.states:
                if st.active:
                    st.weights.append(st.cur_shrinkage)
                    st.sum_weight += st.cur_shrinkage
                    st.tree_shrink.append(st.cur_shrinkage)
                    st.tree_factors.append([])
                else:
                    # keep per-tree lists index-aligned with _dart_base
                    st.tree_shrink.append(float(st.cfg.learning_rate))
                    st.tree_factors.append([])
                    st.weights.append(0.0)
        # eager multiply: its rounding is the standalone
        # `grown.leaf_value * shrinkage` dispatch's rounding
        shrink_dev = self._shrink_dev if self._dart else self._lr_dev
        lv = grown.leaf_value * shrink_dev[:, None]
        self.score = self._vm_upd(self.score, grown.row_leaf, lv, 1.0)
        new_vscores = []
        vb_this = []
        for vb, vs in zip(self.vbins, self.vscores):
            dv = self._vm_walk(vb, grown.split_feature, grown.threshold_bin,
                               grown.nan_bin, grown.cat_member,
                               grown.decision_type, grown.left_child,
                               grown.right_child, lv, grown.num_leaves)
            nvs = vs + dv
            if self._dart:
                # the standalone's (after - before) / w valid base —
                # NOT dv / w: the add rounds, and the base must replay
                # exactly what the score absorbed
                vb_this.append((nvs - vs) / shrink_dev[:, None])
            new_vscores.append(nvs)
        self.vscores = tuple(new_vscores)
        if self._dart:
            self._dart_vb.append(vb_this)
            self._dart_normalize(drops or {})
        grown = grown._replace(row_leaf=jnp.zeros((self.L, 0), jnp.int32))
        self._grown.append(grown)
        leaves = grown.num_leaves
        if hasattr(leaves, "copy_to_host_async"):
            leaves.copy_to_host_async()
        self._leaves.append(leaves)
        self._steps += 1
        for m, st in enumerate(self.states):
            if st.active:
                st.kept_iters = self._steps
        self.record.add_tree(it, 0, grown.hist_passes[0],
                             grown.num_leaves[0])

    def _dummy_qk(self):
        if not hasattr(self, "_qk0"):
            self._qk0 = jnp.zeros((self.L, 2), jnp.uint32)
        return self._qk0

    def _dummy_nk(self):
        if not hasattr(self, "_nk0"):
            self._nk0 = jnp.zeros((self.L, 2, 2), jnp.uint32)
        return self._nk0

    # -- stump stop (lagged, like GBDT.train_one_iter) -----------------------
    def check_stumps(self, it: int) -> None:
        """Before stepping iteration ``it``: a model whose ENTIRE previous
        iteration grew no split stops (the standalone loop pops those
        trees and breaks, gbdt.cpp:430-450).  DART keeps the stump
        iteration's trees — its non-deferred standalone path records them
        before discovering the stop (models/boosting.py _defer_trees)."""
        if it < 1 or it - 1 >= len(self._leaves):
            return
        prev = np.asarray(jax.device_get(self._leaves[it - 1]))
        K = self.K
        for m, st in enumerate(self.states):
            if st.active and all(int(prev[m * K + c]) <= 1
                                 for c in range(K)):
                st.active = False
                st.stop_reason = "no-split"
                if self._dart:
                    st.kept_iters = it
                else:
                    # the stump iteration's trees are popped unless they
                    # are the model's only iteration (they carry the
                    # init bias)
                    st.kept_iters = max(1, it - 1)

    # -- evaluation / early stopping ----------------------------------------
    def _needs_eval(self) -> bool:
        return bool(self.valid_sets)

    def _host_valid_score(self, host_vs: np.ndarray, m: int) -> np.ndarray:
        """Model m's slice of a pulled (L, nv) valid score: (nv,) or the
        standalone's (nv, K) layout for multiclass."""
        if self.K == 1:
            return host_vs[m]
        return host_vs[m * self.K:(m + 1) * self.K].T

    def host_lane_score(self, m: int, rows_dev=None) -> np.ndarray:
        """Model m's current TRAIN score (optionally gathered at device
        row indices): (n,)/(rows,) or (n, K)/(rows, K) for multiclass.
        The CV fast path evaluates held-out metrics on this."""
        if self.K == 1:
            sc = self.score[m] if rows_dev is None else \
                self.score[m][rows_dev]
            return np.asarray(sc)
        sc = self.score[m * self.K:(m + 1) * self.K]
        if rows_dev is not None:
            sc = sc[:, rows_dev]
        return np.asarray(sc).T

    def eval_all(self, it: int, num_boost_round: int) -> None:
        if not self._needs_eval():
            return
        with self.record.phase("eval"):
            host_vs = [np.asarray(vs) for vs in self.vscores]
            for m, st in enumerate(self.states):
                if not st.active:
                    continue
                rows = []
                for vi, (vname, _) in enumerate(self.valid_sets):
                    sc = self._host_valid_score(host_vs[vi], m)
                    for mt in st.metrics_per_valid[vi]:
                        for name, val, hib in mt.eval(sc):
                            rows.append((vname, name, val, hib))
                for dn, en, val, _ in rows:
                    st.history.setdefault(dn, {}).setdefault(
                        en, []).append(val)
                if st.stopper is None and \
                        st.cfg.early_stopping_round and \
                        int(st.cfg.early_stopping_round) > 0:
                    st.stopper = early_stopping(
                        int(st.cfg.early_stopping_round),
                        st.cfg.first_metric_only, verbose=False)
                if st.stopper is not None:
                    env = CallbackEnv(None, {}, it, 0, num_boost_round,
                                      rows)
                    try:
                        st.stopper(env)
                    except EarlyStopException as e:
                        st.active = False
                        st.stop_reason = "early-stop"
                        st.kept_iters = it + 1
                        st.best_iteration = e.best_iteration + 1
                        for dn, en, sc, _ in e.best_score:
                            st.best_score.setdefault(dn, {})[en] = sc

    # -- driver loop ---------------------------------------------------------
    def run(self, num_boost_round: int) -> "BatchTrainer":
        for it in range(num_boost_round):
            self.check_stumps(it)
            if not any(st.active for st in self.states):
                break
            self.step_once(it)
            self.eval_all(it, num_boost_round)
            if not any(st.active for st in self.states):
                break
        return self

    # -- extraction ----------------------------------------------------------
    def finalize(self) -> List[Booster]:
        with self.record.phase("record"):
            pulled = jax.device_get(self._grown)
            scores = self.score
            K = self.K
            boosters = []
            for m, st in enumerate(self.states):
                trees = []
                lr = float(st.cfg.learning_rate)
                for t in range(st.kept_iters):
                    shrink = st.tree_shrink[t] if self._dart else lr
                    for c in range(K):
                        lane = m * K + c
                        g = GrownTree(*[np.asarray(f)[lane]
                                        for f in pulled[t]])
                        tree = _grown_to_tree(g, shrink, self.train_set)
                        if t == 0 and abs(st.bias[c]) > EPSILON:
                            tree.add_bias(st.bias[c])
                        if self._dart:
                            # normalize-time rescales, replayed in the
                            # standalone's chronological order
                            for f in st.tree_factors[t]:
                                tree.shrink(f)
                        trees.append(tree)
                bst = Booster(params=st.params, train_set=self.train_set)
                gb = bst._gbdt
                gb.models = trees
                gb.iter_ = st.kept_iters
                if K == 1:
                    gb.score = scores[m]
                else:
                    gb.score = jnp.swapaxes(
                        scores[m * K:(m + 1) * K], 0, 1)
                if self._dart:
                    kept = st.kept_iters
                    gb._weights = list(st.weights[:kept])
                    gb._sum_weight = float(sum(st.weights[:kept]))
                    gb._cur_shrinkage = st.cur_shrinkage
                bst.best_iteration = st.best_iteration
                bst.best_score = st.best_score
                rec = TrainRecord(meta={
                    "boosting": self.boosting,
                    "objective": str(st.cfg.objective),
                    "tree_learner": "serial",
                    "multitrain_model_index": m,
                    "multitrain_models": self.M,
                    "multitrain_classes": K,
                    "num_leaves": int(st.cfg.num_leaves),
                    "num_data": int(self.n),
                    "num_features": int(self.num_features),
                })
                for t, tr in enumerate(trees):
                    rec.add_tree(t // K, t % K, 0, tr.num_leaves)
                gb.train_record = rec
                boosters.append(bst)
            return boosters
