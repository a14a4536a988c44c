"""Chunked streamed training driver: boosting over a StreamedDataset.

Drives :class:`..ingest.grower.ChunkedWaveGrower` through the boosting
loop with every per-row array host-resident (score, gradients, bag mask,
per-chunk ``row_leaf``) — HBM holds only the bounded chunk ring plus the
wave state, so total rows are limited by disk + host RAM at ~20 B/row,
not by accelerator memory (ROADMAP item 2's 10^8-10^9-row regime).

Envelope (checked, typed errors): numeric features, objective
``regression``/``binary``/``multiclass`` (softmax), boosting
``gbdt``/``goss``/``dart``, no monotone/interaction/forced-split/CEGB/
linear-tree extras; ``stochastic_rounding`` and
``quant_train_renew_leaf`` are forced off (both need full-row device
passes).  Everything else — bagging, ``feature_fraction``, quantized
gradients, boost-from-average — matches the in-core trainer's host-side
sampling streams exactly.  With ``use_quantized_grad=true`` the produced
model text is bit-identical to an in-core ``engine.train`` run of the
same configuration (tests/test_ingest_train.py).

GOSS (arXiv:1806.11248's gradient-based sampling recipe for the
out-of-core tail): the per-tree bag comes from the ONE sampler
(``models.gbdt.goss_sample``, a jitted function on the device: exact
threshold, a draw keyed by (bagging_seed, iteration); the in-core
trainer calls it on its device gradients, this driver and the
multi-model trainer through its host face ``goss_sample_np``; there is
no host random stream), so the streamed run thins exactly the rows the
in-core run thins, warmup included.

DART replays the in-core drop bookkeeping (models/boosting.py DART)
host-side: the per-iteration drop set comes from the same
(drop_seed, iteration) stream, each iteration's raw base predictions
stay as host f32 arrays (~4·iters bytes/row of host RAM — the chunked
regime's resource — mirroring the in-core device cache), and the
drop-subtraction / Normalize re-weighting run as host f32 axpys, the
same IEEE ops the in-core device path executes.  DART does not compose
with checkpoint/resume (the per-tree drop weights are not
reconstructible from model text).

Multiclass softmax grows ``num_class`` trees per iteration from one
per-chunk softmax gradient pass over the host (N, K) score matrix; the
one-hot label matrix stays host-resident and uploads chunk slices per
gradient call.  Ranking objectives stay in-core only: their query
segments straddle chunk boundaries, so per-chunk gradients cannot
reproduce the full-dataset lambdarank pass.

Validation + early stopping: ``valid_sets`` may be StreamedDatasets
(binned against the train set's mappers via ``reference``) or in-core
Datasets.  Each grown tree is walked over the valid set's binned chunks
(the in-core ``_record_tree`` valid update, one bounded chunk at a
time) into a host f32 score; metric eval and the ``early_stopping``
callback then see the same float32 values as the in-core run, so the
stop round matches.

Checkpoint/resume rides the PR-6 bundle format
(:mod:`..resilience.checkpoint`): the bundle's dataset fingerprint is the
StreamedDataset's streamed crc, so a resume against re-streamed chunks
validates end-to-end, and the continuation is bit-identical on the
quantized path.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..basic import Booster
from ..callback import CallbackEnv, EarlyStopException, early_stopping
from ..config import Config
from ..learner.serial import (resolve_hist_impl, split_params_from_config)
from ..metric import create_metrics
from ..models.gbdt import (EPSILON, GBDT, _grown_to_tree, _mappers_equal,
                           _tree_cat_member, bagging_mask_np,
                           feature_mask_np, goss_sample_np, make_walk_fn)
from ..objective import create_objective
from ..objective.binary import BinaryLogloss
from ..objective.multiclass import MulticlassSoftmax
from ..objective.regression import RegressionL2
from ..ops.quantize import quant_levels
from ..resilience.checkpoint import (CKPT_SOFT_KEYS, CKPT_STRUCTURAL_KEYS,
                                     Checkpoint, CheckpointManager,
                                     load_checkpoint)
from ..telemetry.trace import span
from ..utils.log import log_info, log_warning
from ..utils.random import host_rng, rng_checkpoint_state
from .grower import ChunkedWaveGrower, StreamedEnvelopeError
from .stream import StreamedDataset

__all__ = ["train_streamed", "StreamedEnvelopeError"]


def _check_envelope(cfg: Config) -> None:
    bad = []
    if cfg.boosting not in ("gbdt", "goss", "dart"):
        bad.append(f"boosting={cfg.boosting}")
    if cfg.linear_tree:
        bad.append("linear_tree")
    if cfg.monotone_constraints and \
            any(int(v) != 0 for v in cfg.monotone_constraints):
        bad.append("monotone_constraints")
    if cfg.interaction_constraints:
        bad.append("interaction_constraints")
    if cfg.forcedsplits_filename:
        bad.append("forcedsplits_filename")
    if cfg.cegb_penalty_split > 0 or cfg.cegb_penalty_feature_coupled or \
            cfg.cegb_penalty_feature_lazy:
        bad.append("cegb penalties")
    if cfg.feature_fraction_bynode < 1.0:
        bad.append("feature_fraction_bynode")
    if cfg.extra_trees:
        bad.append("extra_trees")
    if cfg.path_smooth > 0:
        bad.append("path_smooth")
    if bad:
        raise StreamedEnvelopeError(
            "chunked streamed training (tpu_ingest_mode=chunked) does not "
            "support: " + ", ".join(bad) + "; train with "
            "tpu_ingest_mode=hbm (in-core from the streamed binned cache) "
            "instead")


def _host_objective(cfg: Config, label: Optional[np.ndarray],
                    weight: Optional[np.ndarray], n: int):
    """Objective with HOST-resident label/weight (no O(N) device copy).
    Mirrors ``ObjectiveFunction.init`` minus the device upload; the
    gradient formulas themselves run per chunk."""
    obj = create_objective(cfg.objective, cfg)
    ok = (type(obj) is BinaryLogloss or
          type(obj) is MulticlassSoftmax or
          (type(obj) is RegressionL2 and not obj.sqrt))
    if not ok:
        raise StreamedEnvelopeError(
            f"chunked streamed training supports objective=regression|"
            f"binary|multiclass (got {cfg.objective}; ranking needs "
            f"full-dataset query segments, multiclassova per-class label "
            f"weights); use tpu_ingest_mode=hbm")
    if label is None:
        raise ValueError(f"objective {obj.name} requires labels")
    label = np.asarray(label, np.float32)
    obj.check_label(label)
    obj.label = label
    obj.weight = None if weight is None else np.asarray(weight, np.float32)
    obj.num_data = n
    if type(obj) is BinaryLogloss:
        # the class-weight computation of BinaryLogloss.init, host-side
        cnt_pos = float((label > 0).sum())
        cnt_neg = float((label <= 0).sum())
        w0 = w1 = 1.0
        if obj.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w0 = cnt_pos / cnt_neg
            else:
                w1 = cnt_neg / cnt_pos
        w1 *= obj.scale_pos_weight
        obj.label_weight = (w0, w1)
    elif type(obj) is MulticlassSoftmax:
        # MulticlassSoftmax.init host-side: the same f32 class-prior
        # sums the in-core init runs over Metadata's f32 label/weight;
        # the one-hot matrix stays a host array (chunk slices upload per
        # gradient call instead of the full (N, K) device residency)
        lab = label.astype(np.int32)
        w = obj.weight
        probs = np.zeros(obj.num_class)
        for c in range(obj.num_class):
            sel = lab == c
            probs[c] = (w[sel].sum() / w.sum()) if w is not None \
                else sel.mean()
        obj.class_init_probs = probs
        obj._onehot_np = np.eye(obj.num_class, dtype=np.float32)[lab]
    return obj


def _chunk_gradients(obj, score_c: np.ndarray, label_c: np.ndarray,
                     weight_c: Optional[np.ndarray],
                     onehot_c: Optional[np.ndarray] = None):
    """One chunk's gradients through the objective's own formula —
    elementwise per row (softmax included: its max/sum reduce within a
    row), so per-chunk evaluation is bit-identical to the in-core
    full-array call."""
    saved = (obj.label, obj.weight)
    saved_oh = getattr(obj, "onehot", None)
    try:
        obj.label = jnp.asarray(label_c, jnp.float32)
        obj.weight = None if weight_c is None else \
            jnp.asarray(weight_c, jnp.float32)
        if onehot_c is not None:
            obj.onehot = jnp.asarray(onehot_c)
        g, h = obj.get_gradients(jnp.asarray(score_c, jnp.float32))
        return np.asarray(g), np.asarray(h)
    finally:
        obj.label, obj.weight = saved
        if onehot_c is not None:
            obj.onehot = saved_oh


def _glue_gbdt(cfg: Config, train_set: StreamedDataset, obj,
               trees: List[Any], k: int = 1) -> GBDT:
    """A host-only GBDT shell carrying the streamed-trained model (for
    model_to_string / Booster surfaces; no device state)."""
    g = GBDT(cfg, None, objective=obj)
    g.train_set = train_set
    g.num_data = train_set.num_data()
    g.num_features = train_set.num_feature()
    g.num_tree_per_iteration = k
    g.models = list(trees)
    g.iter_ = len(trees) // max(1, k)
    return g


class _ValidState:
    """One validation stream: host f32 score matrix + its metric set."""

    __slots__ = ("name", "vset", "nv", "vscore", "metrics")

    def __init__(self, name, vset, nv, vscore, metrics) -> None:
        self.name = name
        self.vset = vset
        self.nv = nv
        self.vscore = vscore
        self.metrics = metrics


def train_streamed(params: Dict[str, Any], train_set: StreamedDataset,
                   num_boost_round: int = 100,
                   valid_sets: Optional[List[Any]] = None,
                   valid_names: Optional[List[str]] = None,
                   resume_from: Optional[str] = None) -> Booster:
    """Boost ``num_boost_round`` trees over a StreamedDataset with
    chunk-accumulated histograms; returns a Booster."""
    cfg = Config(dict(params))
    _check_envelope(cfg)
    if cfg.use_quantized_grad and cfg.stochastic_rounding:
        log_warning("chunked streamed training forces "
                    "stochastic_rounding=false (the per-row rounding "
                    "stream is not chunk-sliceable)")
        cfg.stochastic_rounding = False
    if cfg.use_quantized_grad and cfg.quant_train_renew_leaf:
        log_warning("chunked streamed training forces "
                    "quant_train_renew_leaf=false")
        cfg.quant_train_renew_leaf = False
    train_set.construct(cfg)
    n = train_set.num_data()
    f_used = train_set.num_feature()
    mappers = [train_set.bin_mappers[j] for j in train_set.used_feature_map]
    from ..binning import MissingType
    num_bins = np.array([m.num_bin for m in mappers], np.int32)
    is_cat = np.array([m.is_categorical for m in mappers], bool)
    has_nan = np.array([m.missing_type == MissingType.NAN for m in mappers],
                       bool)
    if np.any(is_cat):
        raise StreamedEnvelopeError(
            "chunked streamed training supports numeric features only; "
            "use tpu_ingest_mode=hbm for categorical data")
    max_bins = int(num_bins.max())
    if cfg.use_quantized_grad:
        # the int32 channel-sum exactness bound GBDT._init_train warns
        # about (single shard here): past it the quantized accumulator
        # can wrap and the chunked==in-core contract is void
        _gq = max(quant_levels(int(cfg.num_grad_quant_bins)))
        if n > (1 << 31) // _gq:
            log_warning(
                f"num_data={n} exceeds the quantized histogram's int32 "
                f"channel-sum exactness bound (2^31/{_gq} rows at "
                f"num_grad_quant_bins={cfg.num_grad_quant_bins}); lower "
                f"num_grad_quant_bins or shard rows across more devices")
    elif n > (1 << 24):
        log_warning(f"num_data={n} exceeds the f32 histogram count "
                    "channel's 16.7M-row exactness range; set "
                    "use_quantized_grad=true for exact int32 counts (and "
                    "the chunked bit-identity contract) at this scale")
    impl = resolve_hist_impl(cfg, wave=True, max_bins=max_bins)
    if impl == "packed4":
        impl = "segment"   # no leaf-channel form (ops/histogram.py)
    if impl == "pallas":
        from ..ops.histogram_pallas import DEFAULT_ROW_BLOCK
        if train_set.chunk_rows % DEFAULT_ROW_BLOCK:
            log_warning(f"chunk_rows={train_set.chunk_rows} is not a "
                        f"multiple of the Pallas row block "
                        f"({DEFAULT_ROW_BLOCK}); using the XLA onehot "
                        f"histogram path")
            impl = "onehot"
    sp = split_params_from_config(cfg, num_bins, is_cat)
    gq_max, hq_max = quant_levels(int(cfg.num_grad_quant_bins))
    grower = ChunkedWaveGrower(
        num_leaves=int(cfg.num_leaves), num_features=f_used,
        max_bins=max_bins, max_depth=int(cfg.max_depth), split_params=sp,
        num_bins=num_bins, has_nan=has_nan, hist_impl=impl,
        quantized=bool(cfg.use_quantized_grad), gq_max=gq_max,
        hq_max=hq_max, wave_size=int(cfg.tpu_wave_size),
        interpret=None, pipeline=(None if cfg.tpu_pallas_pipeline == "auto"
                                  else str(cfg.tpu_pallas_pipeline)))

    md = train_set.metadata
    obj = _host_objective(cfg, md.label, md.weight, n)
    label32 = obj.label
    weight32 = obj.weight
    K = int(obj.num_model_per_iteration)
    shape = (n,) if K == 1 else (n, K)
    onehot_np = getattr(obj, "_onehot_np", None)

    # ---- initial scores (GBDT._init_train's score0 logic) -----------------
    score = np.zeros(shape, np.float32)
    pending_bias = np.zeros(K)
    if md.init_score is not None:
        score = score + md.init_score.reshape(shape).astype(np.float32)
    elif cfg.boost_from_average:
        for cid in range(K):
            b = obj.boost_from_score(cid)
            pending_bias[cid] = b
            if abs(b) > EPSILON:
                log_info(f"Start training from score {b:.6f}")
        score = score + (np.float32(pending_bias[0]) if K == 1 else
                         pending_bias[None, :].astype(np.float32))

    # ---- checkpoint / resume ----------------------------------------------
    ckpt_dir = str(cfg.checkpoint_dir or "")
    if not ckpt_dir and int(cfg.snapshot_freq) > 0:
        ckpt_dir = f"{cfg.output_model}.ckpt"
    manager = CheckpointManager(ckpt_dir, int(cfg.checkpoint_keep)) \
        if ckpt_dir else None
    freq = int(cfg.snapshot_freq) if int(cfg.snapshot_freq) > 0 else \
        max(1, num_boost_round // 100)
    if resume_from is None and str(cfg.resume).strip():
        want = str(cfg.resume).strip()
        if want in ("latest", "auto"):
            resume_from = manager.latest_path() if manager else None
            if resume_from is None and not manager:
                raise ValueError("resume=latest needs snapshot_freq>0 or "
                                 "checkpoint_dir")
        else:
            resume_from = want
    if cfg.boosting == "dart" and (manager is not None or resume_from):
        raise StreamedEnvelopeError(
            "chunked dart training does not support checkpoint/resume: "
            "the per-tree drop weights cannot be reconstructed from the "
            "checkpointed model text; drop checkpoint_dir/snapshot_freq/"
            "resume or use tpu_ingest_mode=hbm")
    trees: List[Any] = []
    start_iter = 0
    if resume_from:
        ckpt = load_checkpoint(str(resume_from))
        ckpt.validate_dataset(train_set)
        ckpt.validate_config(cfg)
        from ..models.model_text import string_to_model
        loaded = string_to_model(ckpt.model_text, cfg)
        trees = list(loaded.models)
        start_iter = int(ckpt.iteration)
        score = np.asarray(ckpt.score, np.float32).reshape(shape).copy()
        log_info(f"train_streamed: resumed at iteration {start_iter} "
                 f"from {resume_from}")

    def _save_ckpt(it: int) -> None:
        if manager is None:
            return
        text = _glue_gbdt(cfg, train_set, obj, trees, K) \
            .save_model_to_string()
        manager.save(Checkpoint(
            iteration=it, model_text=text, score=score.copy(),
            rng_state=rng_checkpoint_state(cfg),
            fingerprint=train_set.fingerprint(),
            params={k: getattr(cfg, k)
                    for k in CKPT_STRUCTURAL_KEYS + CKPT_SOFT_KEYS}))

    # ---- validation streams (in-core add_valid, host-resident) ------------
    walk = make_walk_fn(None, True)   # numeric-only envelope: dense walk

    def _vchunks(vs):
        if getattr(vs, "is_streamed", False):
            for ci in range(vs.num_chunks()):
                lo, hi = vs.chunk_bounds(ci)
                yield lo, hi, vs.binned_chunk(ci)
        else:
            yield 0, vs.num_data(), np.asarray(vs.X_binned)

    def _valid_delta(vst, targs):
        """One tree's walk over the valid set, chunk at a time (the
        in-core _record_tree valid update on bounded device memory;
        eager like the in-core valid walk, so the values are the same
        f32 the in-core run records)."""
        out = np.empty(vst.nv, np.float32)
        for lo, hi, bins in _vchunks(vst.vset):
            out[lo:hi] = np.asarray(walk(jnp.asarray(bins), *targs))
        return out

    valids: List[_ValidState] = []
    provide_train = bool(cfg.is_provide_training_metric)
    if valid_sets:
        if not isinstance(valid_sets, (list, tuple)):
            valid_sets = [valid_sets]
        for i, vs in enumerate(valid_sets):
            if vs is train_set:
                provide_train = True   # engine.train's vs-is-train contract
                continue
            nm = (valid_names[i] if valid_names is not None and
                  i < len(valid_names) else f"valid_{i}")
            if not vs.constructed and getattr(vs, "reference", None) is None:
                vs.reference = train_set
            vs.construct(cfg)
            if vs.bin_mappers is not train_set.bin_mappers and \
                    not _mappers_equal(vs.bin_mappers, train_set.bin_mappers):
                raise ValueError(
                    "cannot add validation data: it was constructed "
                    "without reference to the training Dataset (different "
                    "bin mappers); pass reference= when creating it")
            if vs.num_feature() != f_used:
                raise ValueError(
                    "validation set feature count differs from train")
            nv = vs.num_data()
            vshape = (nv,) if K == 1 else (nv, K)
            v0 = np.zeros(vshape, np.float32)
            if vs.metadata.init_score is not None:
                v0 = v0 + vs.metadata.init_score.reshape(vshape).astype(
                    np.float32)
            elif cfg.boost_from_average:
                v0 = v0 + (np.float32(pending_bias[0]) if K == 1 else
                           pending_bias[None, :].astype(np.float32))
            mts = create_metrics(cfg)
            for m in mts:
                m.init(vs.metadata, nv)
            vst = _ValidState(nm, vs, nv, v0, mts)
            if trees:   # resumed: fold loaded trees into the valid score
                for t, tree in enumerate(trees):
                    cid = t % K
                    targs = (jnp.asarray(tree.split_feature),
                             jnp.asarray(tree.threshold_bin),
                             jnp.asarray(tree.nan_bin),
                             _tree_cat_member(tree),
                             jnp.asarray(tree.decision_type.astype(np.int32)),
                             jnp.asarray(tree.left_child),
                             jnp.asarray(tree.right_child),
                             jnp.asarray(tree.leaf_value, dtype=jnp.float32),
                             jnp.asarray(tree.num_leaves, dtype=jnp.int32))
                    delta = _valid_delta(vst, targs)
                    if K == 1:
                        vst.vscore = vst.vscore + delta
                    else:
                        vst.vscore[:, cid] = vst.vscore[:, cid] + delta
            valids.append(vst)
    train_metrics: List[Any] = []
    if provide_train:
        train_metrics = create_metrics(cfg)
        for m in train_metrics:
            m.init(md, n)

    # ---- flight recorder (telemetry/flight.py) ----------------------------
    # the chunked path is the one where the per-event h2d byte counter
    # actually moves; the tape dumps next to the checkpoints on a crash
    from ..telemetry.flight import FlightRecorder
    flight = FlightRecorder(
        capacity=int(cfg.flight_events), enabled=bool(cfg.flight_recorder),
        meta={"boosting": str(cfg.boosting), "objective": str(cfg.objective),
              "num_data": int(n), "ingest_mode": "chunked"})

    def _flight_dump(reason: str) -> None:
        out_dir = str(cfg.flight_dir) or ckpt_dir
        if not flight.enabled or len(flight) == 0 or not out_dir:
            return
        try:
            os.makedirs(out_dir, exist_ok=True)
            flight.dump(os.path.join(out_dir, "flight.jsonl"),
                        reason=reason)
        except OSError as exc:
            log_warning(f"flight recorder dump failed: {exc}")

    # ---- boosting loop -----------------------------------------------------
    goss = cfg.boosting == "goss"
    dart = cfg.boosting == "dart"
    if goss and cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0:
        # in-core GOSS ignores bagging too (models/boosting.py GOSS)
        log_warning("cannot use bagging in GOSS (ignored)")
    grad = np.empty(shape, np.float32)
    hess = np.empty(shape, np.float32)
    completed = start_iter

    # DART host state (models/boosting.py DART, host-resident): the raw
    # per-iteration base predictions + per-valid unshrunk deltas back the
    # O(N) drop/Normalize axpys
    dart_weights: List[float] = []
    dart_sum_weight = 0.0
    dart_base: List[np.ndarray] = []
    dart_vb: List[List[np.ndarray]] = []
    cur_shrinkage = float(cfg.learning_rate)

    def _dart_drop(t: int) -> List[int]:
        """The in-core DART drop selection, verbatim (one host_rng
        stream per (drop_seed, iteration))."""
        rng = host_rng(cfg.drop_seed, t)
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
                inv_avg = t / max(dart_sum_weight, 1e-12)
                p = cfg.drop_rate
                if cfg.max_drop > 0:
                    p = min(p, cfg.max_drop * inv_avg /
                            max(dart_sum_weight, 1e-12))
                for i in range(t):
                    if rng.random() < p * dart_weights[i] * inv_avg:
                        drop.append(i)
                        if cfg.max_drop > 0 and len(drop) >= cfg.max_drop:
                            break
        return drop

    def _dart_normalize(drop: List[int]) -> None:
        """The in-core DART Normalize: shrink dropped host trees by
        k/(k+1) (xgboost mode k/(k+lr)), re-add the train score at the
        new weight, adjust valid scores by the weight delta."""
        nonlocal score, dart_sum_weight
        kd = float(len(drop))
        if kd == 0:
            return
        lr = float(cfg.learning_rate)
        factor = kd / (kd + lr) if cfg.xgboost_dart_mode else kd / (kd + 1.0)
        for d in drop:
            old_w = dart_weights[d]
            new_w = old_w * factor
            dart_weights[d] = new_w
            dart_sum_weight -= old_w - new_w
            for c in range(K):
                trees[d * K + c].shrink(factor)
            score = score + dart_base[d] * np.float32(new_w)
            for vi, vst in enumerate(valids):
                vst.vscore = vst.vscore + \
                    dart_vb[d][vi] * np.float32(new_w - old_w)

    def _tree_args(grown, lv):
        return (jnp.asarray(grown.split_feature),
                jnp.asarray(grown.threshold_bin),
                jnp.asarray(grown.nan_bin), jnp.asarray(grown.cat_member),
                jnp.asarray(grown.decision_type),
                jnp.asarray(grown.left_child),
                jnp.asarray(grown.right_child),
                jnp.asarray(lv, jnp.float32),
                jnp.asarray(grown.num_leaves, jnp.int32))

    def _one_iter(it: int) -> bool:
        """One streamed boosting iteration; True = stop (no more
        splittable leaves)."""
        nonlocal completed, score, cur_shrinkage, dart_sum_weight
        first_iter = it == start_iter and not trees
        drop: List[int] = []
        if dart:
            # drop BEFORE gradients (dart.hpp DroppingTrees): gradients
            # see the thinned ensemble's score
            drop = _dart_drop(it)
            for d in drop:
                score = score - dart_base[d] * np.float32(dart_weights[d])
            kd = float(len(drop))
            lr = float(cfg.learning_rate)
            if cfg.xgboost_dart_mode:
                cur_shrinkage = lr if not drop else lr / (lr + kd)
            else:
                cur_shrinkage = lr / (1.0 + kd)
        shrinkage = cur_shrinkage if dart else float(cfg.learning_rate)
        for i in range(train_set.num_chunks()):
            lo, hi = train_set.chunk_bounds(i)
            g, h = _chunk_gradients(
                obj, score[lo:hi], label32[lo:hi],
                None if weight32 is None else weight32[lo:hi],
                None if onehot_np is None else onehot_np[lo:hi])
            grad[lo:hi] = g
            hess[lo:hi] = h
        gw, hw = grad, hess
        if goss:
            # GOSS replaces bagging (in-core GOSS overrides
            # _prepare_iter_sampling and never draws a bag); the draw is
            # the one jitted sampler's host face, warmup handled inside
            mask = np.ones(n, np.float32)
            gm = goss_sample_np(cfg, grad, hess, it)
            if gm is not None:
                mask, mult = gm
                scale = mult if K == 1 else mult[:, None]
                gw = grad * scale
                hw = hess * scale
        else:
            mask = bagging_mask_np(
                cfg, n, it,
                label=(np.asarray(label32) if cfg.objective == "binary"
                       else None))
            mask = np.ones(n, np.float32) if mask is None else mask
        fmask = feature_mask_np(cfg, f_used, it)
        grown_cls = []
        for cid in range(K):
            g_c = gw if K == 1 else np.ascontiguousarray(gw[:, cid])
            h_c = hw if K == 1 else np.ascontiguousarray(hw[:, cid])
            grown, rl_chunks = grower.grow(train_set, g_c, h_c, mask,
                                           feature_mask=fmask)
            grown_cls.append((grown, rl_chunks))
        all_stump = all(int(g.num_leaves) <= 1 for g, _ in grown_cls)
        if not dart and all_stump and trees:
            # the in-core deferred-stump pop, without the round trip:
            # an all-stump iteration past the first never enters the
            # model (first iteration kept — it carries boost_from_average)
            log_warning("Stopped training because there are no more "
                        "leaves that meet the split requirements")
            return True
        base_this: Optional[np.ndarray] = None
        vb_this = [np.zeros_like(v.vscore) for v in valids] if dart else None
        for cid, (grown, rl_chunks) in enumerate(grown_cls):
            lv_raw = np.asarray(grown.leaf_value, np.float32)
            lv = lv_raw * np.float32(shrinkage)
            tree = _grown_to_tree(grown, shrinkage, train_set)
            bias = pending_bias[cid] if first_iter else 0.0
            if abs(bias) > EPSILON:
                tree.add_bias(bias)
            trees.append(tree)
            # score update: the in-core _score_update_impl's
            # score + lv[row_leaf], per chunk, host f32 (same IEEE ops)
            for i, rl_c in enumerate(rl_chunks):
                lo, hi = train_set.chunk_bounds(i)
                step = lv[rl_c.astype(np.int64)]
                if K == 1:
                    score[lo:hi] = score[lo:hi] + step
                else:
                    score[lo:hi, cid] = score[lo:hi, cid] + step
            if dart:
                if base_this is None:
                    base_this = np.zeros(shape, np.float32)
                for i, rl_c in enumerate(rl_chunks):
                    lo, hi = train_set.chunk_bounds(i)
                    b = lv_raw[rl_c.astype(np.int64)]
                    if K == 1:
                        base_this[lo:hi] = b
                    else:
                        base_this[lo:hi, cid] = b
            if valids:
                targs = _tree_args(grown, lv)
                for vi, vst in enumerate(valids):
                    delta = _valid_delta(vst, targs)
                    if K == 1:
                        vst.vscore = vst.vscore + delta
                    else:
                        vst.vscore[:, cid] = vst.vscore[:, cid] + delta
                    if dart:
                        # raw valid base = shrunk delta / weight, the
                        # in-core _record_tree bookkeeping (NOT a
                        # re-walk with raw lv: (lv*w)/w can drift an
                        # ulp, and the in-core Normalize uses exactly
                        # this quotient)
                        dv = delta / np.float32(shrinkage)
                        if K == 1:
                            vb_this[vi] = vb_this[vi] + dv
                        else:
                            vb_this[vi][:, cid] = vb_this[vi][:, cid] + dv
        if dart:
            dart_base.append(base_this if base_this is not None
                             else np.zeros(shape, np.float32))
            dart_weights.append(float(shrinkage))
            dart_sum_weight += float(shrinkage)
            dart_vb.append(vb_this or [])
            _dart_normalize(drop)
        completed = it + 1
        flight.note_iter(completed,
                         num_leaves=int(grown_cls[-1][0].num_leaves))
        if all_stump:
            # first gbdt/goss iteration, or any dart iteration (dart is
            # non-deferred in-core: stump trees stay recorded; stop
            # after Normalize)
            log_warning("Stopped training because there are no more "
                        "leaves that meet the split requirements")
            return True
        if manager is not None and completed % freq == 0:
            _save_ckpt(completed)
        return False

    stopper = None
    if valids and cfg.early_stopping_round and \
            int(cfg.early_stopping_round) > 0:
        stopper = early_stopping(int(cfg.early_stopping_round),
                                 bool(cfg.first_metric_only),
                                 verbose=cfg.verbosity >= 1)
    best_iteration = -1
    best_score: Dict[str, Dict[str, float]] = {}
    try:
        for it in range(start_iter, num_boost_round):
            with span("ingest/train/iteration"):
                if _one_iter(it):
                    break
            if valids or train_metrics:
                # eval AFTER the iteration, in engine.train's stream
                # order (training metrics first), on the SAME f32 score
                # values the in-core run holds -> same stop round
                results = []
                for m in train_metrics:
                    for mname, val, hib in m.eval(score):
                        results.append(("training", mname, val, hib))
                for vst in valids:
                    for m in vst.metrics:
                        for mname, val, hib in m.eval(vst.vscore):
                            results.append((vst.name, mname, val, hib))
                flight.note_eval(it + 1, results)
                if stopper is not None:
                    try:
                        stopper(CallbackEnv(None, dict(params), it, 0,
                                            num_boost_round, results))
                    except EarlyStopException as e:
                        best_iteration = e.best_iteration + 1
                        for ds_name, eval_name, sc, _ in e.best_score:
                            best_score.setdefault(
                                ds_name, {})[eval_name] = sc
                        break
    except (Exception, KeyboardInterrupt):
        _flight_dump("crash")
        raise
    if manager is not None:
        _save_ckpt(completed)
    if str(cfg.flight_dir):
        _flight_dump("completed")

    gbdt = _glue_gbdt(cfg, train_set, obj, trees, K)
    bst = Booster.__new__(Booster)
    bst.params = dict(params)
    bst.best_iteration = best_iteration
    bst.best_score = best_score
    bst._train_data_name = "training"
    bst.config = cfg
    bst._gbdt = gbdt
    return bst
