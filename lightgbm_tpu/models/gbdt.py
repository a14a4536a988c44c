"""GBDT boosting driver.

TPU-native re-implementation of the reference boosting layer
(reference: src/boosting/gbdt.cpp — ``Train`` loop at :264, ``TrainOneIter``
at :369, bagging at :228, ``BoostFromAverage`` at :344 with the init score
folded into the first tree via AddBias at :414-427, score updates via
ScoreUpdater at :491, metric output at :517).

The boosting loop is host-driven; everything per-iteration — gradients,
sampling, tree growth, score update — runs as jitted device computations on
device-resident arrays.  Host<->device traffic per iteration is only the
handful of tree description arrays (O(num_leaves)) pulled back to record the
model, plus metric scalars when evaluation runs.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import contracts as _contracts
from ..config import Config
from ..dataset import Dataset
from ..learner.serial import GrownTree, SerialTreeLearner
from ..metric import Metric, create_metrics
from ..objective import ObjectiveFunction, create_objective
from ..telemetry.trace import span, timed_span
from ..telemetry.train_record import TrainRecord, set_last_train_record
from ..utils.log import log_info, log_warning
from ..utils.random import host_rng
from .tree import Tree, TreeBatch, pad_rows, predict_raw
from ..ops.split import leaf_output as _leaf_output_fn

EPSILON = 1e-12


def _grown_to_tree(grown: GrownTree, shrinkage: float, dataset: Dataset,
                   leaf_value_override: Optional[np.ndarray] = None) -> Tree:
    """Pull one grown tree to host, attach raw-value thresholds and
    categorical bitsets (reference tree.h:85 SplitCategorical: cat nodes
    store a rank into cat_boundaries; cat_threshold words are a bitset over
    raw category values)."""
    num_leaves = int(grown.num_leaves)
    split_feature = np.asarray(grown.split_feature)
    threshold_bin = np.asarray(grown.threshold_bin)
    decision_type = np.asarray(grown.decision_type)
    member = np.asarray(grown.cat_member)
    mappers = [dataset.bin_mappers[j] for j in dataset.used_feature_map]
    thresh = np.zeros(len(split_feature), dtype=np.float64)
    cat_boundaries: List[int] = [0]
    cat_words: List[int] = []
    has_cat = False
    for i in range(num_leaves - 1):
        f = int(split_feature[i])
        if f < 0:
            continue
        from .tree import CAT_MASK as _CM
        if decision_type[i] & _CM:
            has_cat = True
            bins = np.nonzero(member[i])[0]
            b2c = mappers[f].bin_to_cat
            cats = [int(b2c[b]) for b in bins if b < len(b2c)] or [0]
            nw = max(cats) // 32 + 1
            wd = np.zeros(nw, np.uint32)
            for c in cats:
                wd[c // 32] |= np.uint32(1 << (c % 32))
            thresh[i] = float(len(cat_boundaries) - 1)   # rank
            cat_words.extend(int(w) for w in wd)
            cat_boundaries.append(len(cat_words))
        else:
            thresh[i] = mappers[f].bin_to_value(int(threshold_bin[i]))
    tree = Tree(
        num_leaves=max(num_leaves, 1),
        split_feature=split_feature.astype(np.int32),
        threshold_bin=threshold_bin.astype(np.int32),
        nan_bin=np.asarray(grown.nan_bin, dtype=np.int32),
        threshold=thresh,
        decision_type=np.asarray(grown.decision_type).astype(np.uint8),
        left_child=np.asarray(grown.left_child).astype(np.int32),
        right_child=np.asarray(grown.right_child).astype(np.int32),
        split_gain=np.asarray(grown.split_gain),
        internal_value=np.asarray(grown.internal_value, dtype=np.float64),
        internal_weight=np.asarray(grown.internal_weight, dtype=np.float64),
        internal_count=np.asarray(grown.internal_count).astype(np.int64),
        leaf_value=(np.asarray(grown.leaf_value, dtype=np.float64)
                    if leaf_value_override is None
                    else np.asarray(leaf_value_override, dtype=np.float64)),
        leaf_weight=np.asarray(grown.leaf_weight, dtype=np.float64),
        leaf_count=np.asarray(grown.leaf_count).astype(np.int64),
        cat_boundaries=(np.asarray(cat_boundaries, np.int32)
                        if has_cat else None),
        cat_threshold=(np.asarray(cat_words, np.uint32)
                       if has_cat else None),
        cat_member_bins=member[:max(num_leaves - 1, 1)] if has_cat else None,
    )
    if shrinkage != 1.0:
        tree.shrink(shrinkage)
    return tree


def _tree_cat_member(tree: Tree) -> jnp.ndarray:
    """Binned categorical membership for a host tree's device walk (width-1
    zeros when the tree has no categorical nodes)."""
    if tree.cat_member_bins is not None:
        return jnp.asarray(tree.cat_member_bins)
    return jnp.zeros((max(len(tree.split_feature), 1), 1), jnp.bool_)


def _mappers_equal(a, b) -> bool:
    """Bin-mapper alignment by VALUE (reference dataset.h:304 CheckAlign) —
    identity fails for equal mappers reloaded from the binary dataset
    cache."""
    if len(a) != len(b):
        return False
    for ma, mb in zip(a, b):
        if (ma.num_bin != mb.num_bin or
                ma.is_categorical != mb.is_categorical or
                ma.missing_type != mb.missing_type):
            return False
        if ma.bin_upper_bound is not None or mb.bin_upper_bound is not None:
            if ma.bin_upper_bound is None or mb.bin_upper_bound is None or \
                    not np.array_equal(ma.bin_upper_bound,
                                       mb.bin_upper_bound):
                return False
        if ma.cat_to_bin != mb.cat_to_bin:
            return False
    return True


def _name_refused_kernels(exc: Exception) -> None:
    """A kernel Mosaic refuses fails the compile of the whole grower, and
    its message names an MLIR op, not the kernel.  Re-raise with the
    kernels (kind + static shape) this process has traced, so the error
    says what was being compiled; anything else passes through."""
    if "Mosaic" not in f"{type(exc).__name__}: {exc}":
        return
    from ..ops.histogram_pallas import traced_kernels
    raise RuntimeError(
        "Mosaic refused a Pallas kernel while compiling the tree grower; "
        f"kernels traced so far: {', '.join(traced_kernels())}. "
        f"Compiler message: {exc}") from exc


def _score_update_impl(score, row_leaf, leaf_value, shrinkage):
    """score += shrinkage * leaf_value[row_leaf] — training-set score update
    using the grower's final leaf assignment (replaces the reference's
    ScoreUpdater::AddScore tree walk for train data, score_updater.hpp:54).

    Named ``_update_score_impl`` until it got its scope: the persistent
    compile cache's key leaves metadata out, so a cache that holds the
    scope-less executable would go on serving it under the old module name."""
    with jax.named_scope("lgbm.score_update"):
        return score + shrinkage * leaf_value[row_leaf]


# Undonated entry: the multitrain driver vmaps this over the model axis
# (donation annotations do not survive inner-jit batching).
_update_score_by_leaf = jax.jit(_score_update_impl)

# Standalone boosting path: the incoming (N,)/(N,) column score buffer is
# dead after the call (``self.score`` is rebound to the result; the
# multiclass call site passes a fresh slice), so the buffer is donated
# and XLA updates the score in place instead of allocating a second
# N-row buffer per tree.  The aliasing contract — donated input aval
# must exactly match an output aval, or XLA silently copies — is
# machine-checked by ``lint-trace``'s donation rule via the declaration
# below.  TPU-only at dispatch: the XLA:CPU runtime in this jax version
# frees a donated buffer while earlier in-flight consumers of the same
# score array may still be reading it (observed as a hard runtime abort
# in the capi update path); on TPU the aliasing is what buys back an
# N-row HBM buffer per tree.
SCORE_DONATE_ARGNUMS = (0,)
_update_score_by_leaf_donated = jax.jit(
    _score_update_impl, donate_argnums=SCORE_DONATE_ARGNUMS)


def _score_select_impl(score, row_leaf, leaf_value, shrinkage, *,
                       interpret=None):
    """:func:`_score_update_impl` with the lookup as a streaming select
    over the rows (ops/histogram_pallas.py ``score_update_pallas``): the
    same bits, since a select hands a leaf's f32 value on untouched."""
    from ..ops.histogram_pallas import score_update_pallas
    with jax.named_scope("lgbm.score_update"):
        return score_update_pallas(score, row_leaf, shrinkage * leaf_value,
                                   interpret=interpret)


_update_score_by_select_donated = jax.jit(
    _score_select_impl, donate_argnums=SCORE_DONATE_ARGNUMS)


@functools.lru_cache(maxsize=None)
def _update_score_by_select_sharded(mesh, axis: str, interpret=None):
    """The select over row shards of ``mesh``: a ``pallas_call`` is not
    partitioned by GSPMD, so each chip runs it on its own rows (score and
    ``row_leaf`` sharded alike, the leaf values replicated; no
    collective)."""
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        functools.partial(_score_select_impl, interpret=interpret),
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P()), out_specs=P(axis),
        check_vma=False), donate_argnums=SCORE_DONATE_ARGNUMS)


# The select costs rows x leaves, the gather rows whatever the leaves.  On
# a TPU v5e at 21.25M rows (scripts/bench_score_update.py; PERF.md section
# 6, PR 35) the select took 2.17 ms at 255 leaves, 8.30 at 1023 and 32.88
# at 4095 (8.0 us a leaf), the gather 175.1 / 113.7 / 152.1 ms: the lines
# cross near 14,000 leaves, and the table (SMEM) is kept to half of that.
SCORE_SELECT_MAX_LEAVES = 8192


def score_update_lowering(backend: str, num_leaves: int) -> str:
    """``"select"`` or ``"gather"``: which lowering the training-set score
    update takes, from what the program can observe — the backend (off
    the TPU a ``pallas_call`` is interpreted, and the gather is what
    XLA:CPU does well) and the static length of ``leaf_value``."""
    if backend == "tpu" and num_leaves <= SCORE_SELECT_MAX_LEAVES:
        return "select"
    return "gather"


_contracts.donation_contract(
    "gbdt/score_update", lambda: _update_score_by_leaf_donated,
    SCORE_DONATE_ARGNUMS,
    lambda: (jnp.zeros((64,), jnp.float32), jnp.zeros((64,), jnp.int32),
             jnp.zeros((8,), jnp.float32), np.float32(0.1)))
_contracts.donation_contract(
    "gbdt/score_update_select", lambda: _update_score_by_select_donated,
    SCORE_DONATE_ARGNUMS,
    lambda: (jnp.zeros((64,), jnp.float32), jnp.zeros((4096,), jnp.int32),
             jnp.zeros((8,), jnp.float32), np.float32(0.1)))


# -- per-iteration sampling (pure functions of (config, iter)) --------------
# Single-sourced here so the multi-model trainer (lightgbm_tpu/multitrain/)
# draws bit-identical bags/feature sets for every model in a batch: a
# train_many() variant and a standalone train() with the same seeds MUST
# sample the same rows/features or the bit-identity contract breaks.
# Bagging and feature masks are host draws; the GOSS draw is one jitted
# function on the device, with a host face for the host-side trainers.

def bagging_mask_np(cfg, n: int, iteration: int,
                    label: Optional[np.ndarray] = None,
                    rows: Optional[np.ndarray] = None) -> Optional[np.ndarray]:
    """Per-iteration bagging mask (gbdt.cpp:228 Bagging, resampled every
    bagging_freq iters with a deterministic per-block seed).

    Returns a float32 (n,) 0/1 mask, or None when bagging is inactive
    (caller keeps/creates the all-ones mask).  ``rows`` restricts the draw
    to those row indices: the generator then samples positions in
    ``range(len(rows))`` — exactly the draws a standalone run on the
    compacted ``dataset[rows]`` would make — and scatters back to full
    length (the masked-fold CV path of train_many)."""
    if not cfg.bagging_active:
        return None
    pos_neg = (cfg.objective == "binary" and
               (cfg.pos_bagging_fraction < 1.0 or
                cfg.neg_bagging_fraction < 1.0))
    block = iteration // cfg.bagging_freq
    rng = host_rng(cfg.bagging_seed, block)
    nn = n if rows is None else len(rows)
    sub = np.zeros(nn, np.float32)
    if pos_neg:
        # balanced bagging (gbdt.cpp:199 BaggingHelper pos/neg fractions)
        lab = label if rows is None else label[rows]
        pos = np.nonzero(lab > 0)[0]
        neg = np.nonzero(lab <= 0)[0]
        kp = int(len(pos) * cfg.pos_bagging_fraction)
        kn = int(len(neg) * cfg.neg_bagging_fraction)
        if kp:
            sub[rng.choice(pos, size=kp, replace=False)] = 1.0
        if kn:
            sub[rng.choice(neg, size=kn, replace=False)] = 1.0
    else:
        k = int(nn * cfg.bagging_fraction)
        sub[rng.choice(nn, size=k, replace=False)] = 1.0
    if rows is None:
        return sub
    mask = np.zeros(n, np.float32)
    mask[rows] = sub
    return mask


# per-row classes of a GOSS draw
GOSS_OUT, GOSS_TOP, GOSS_REST = 0, 1, 2


def goss_rates(cfg, iteration: int) -> Optional[Tuple[float, float]]:
    """``(top_rate, other_rate)`` where GOSS samples this iteration; None
    during the first 1/learning_rate iterations (goss.hpp:157) and where
    top_rate + other_rate >= 1.  Decided on the host, from the iteration
    alone."""
    a, b = float(cfg.top_rate), float(cfg.other_rate)
    warmup = int(1.0 / max(float(cfg.learning_rate), 1e-12))
    if iteration < warmup or a + b >= 1.0:
        return None
    return a, b


def _kth_largest_u32(u: jnp.ndarray, k: int) -> jnp.ndarray:
    """The exact k-th largest of a uint32 vector: the largest ``t`` with
    ``count(u >= t) >= k``, found bit by bit from the top (a radix select
    of radix 2: 32 counting passes, no sort, and a row-sharded ``u`` costs
    one scalar sum a pass)."""
    def body(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(jnp.sum(u >= cand, dtype=jnp.int32) >= k, cand, t)
    return jax.lax.fori_loop(0, 32, body, jnp.uint32(0))


@functools.partial(jax.jit, static_argnames=("top_rate", "other_rate",
                                             "bagging_seed"))
def goss_sample(grad: jnp.ndarray, hess: jnp.ndarray, iteration, *,
                top_rate: float, other_rate: float, bagging_seed: int):
    """THE GOSS draw (goss.hpp:103-152), one jitted program on whatever
    device holds ``grad`` / ``hess`` ((n,) or, multiclass, (n, K) float32).

    Score ``|g*h|`` in float32, summed over classes (goss.hpp:118);
    ``k = max(1, int(n * top_rate))``; the threshold is the EXACT k-th
    largest score (non-negative floats order as their bit patterns, see
    :func:`_kth_largest_u32`) and every row at or above it is kept, ties
    included; every other row is kept independently with probability
    ``other_rate / (1 - top_rate)`` from a key that depends on
    ``(bagging_seed, iteration)`` alone, and carries the multiplier
    ``(1 - top_rate) / other_rate``.

    Returns ``(cls, mask, grad, hess, sampled_rows)``: the per-row class
    (uint8: ``GOSS_OUT`` out of the bag, ``GOSS_TOP`` kept at weight 1,
    ``GOSS_REST`` kept at the multiplier), the float32 0/1 bag mask, the
    gradients and hessians times their rows' multipliers, and the rows in
    the bag (int32 scalar).  Row-sharded inputs give row-sharded outputs
    and the same classes: the threshold is global, and the draw does not
    depend on the sharding (``jax_threefry_partitionable``)."""
    a, b = float(top_rate), float(other_rate)
    with jax.named_scope("lgbm.goss.sample"):
        score = jnp.abs(grad * hess)
        if score.ndim == 2:
            score = score.sum(axis=1)
        n = score.shape[0]
        k = max(1, int(n * a))
        u = jax.lax.bitcast_convert_type(score.astype(jnp.float32),
                                         jnp.uint32)
        top = u >= _kth_largest_u32(u, k)
        key = jax.random.fold_in(jax.random.PRNGKey(bagging_seed), iteration)
        rest_p = b / max(1.0 - a, 1e-12)
        keep_rest = ~top & (jax.random.uniform(key, (n,)) < rest_p)
        cls = jnp.where(top, GOSS_TOP,
                        jnp.where(keep_rest, GOSS_REST, GOSS_OUT)
                        ).astype(jnp.uint8)
        mult = jnp.where(keep_rest, jnp.float32((1.0 - a) / max(b, 1e-12)),
                         jnp.float32(1.0))
        if grad.ndim == 2:
            mult = mult[:, None]
        in_bag = top | keep_rest
        return (cls, in_bag.astype(jnp.float32), grad * mult, hess * mult,
                jnp.sum(in_bag, dtype=jnp.int32))


def goss_sample_np(cfg, grad: np.ndarray, hess: np.ndarray, iteration: int,
                   rows: Optional[np.ndarray] = None):
    """The host face of :func:`goss_sample`, for the trainers that keep
    their gradients on the host (the chunked streamed driver,
    ingest/train.py, and the multi-model trainer, multitrain/batched.py):
    the SAME jitted function, its classes fetched.  So all three trainers
    thin exactly the same rows and the bit-identity contracts hold across
    them; there is no second sampler and no host random stream.  ``rows``
    restricts the draw to those row indices (the masked-fold CV path):
    threshold and draw are computed over the compacted subset — exactly
    what a standalone run on ``dataset[rows]`` would draw — and scattered
    back to full length.

    Returns ``(mask, mult)`` float32 (n,) arrays — 0/1 survivorship and the
    per-row gradient multiplier — or None when sampling is inactive this
    iteration (warmup, or top_rate+other_rate >= 1)."""
    rates = goss_rates(cfg, iteration)
    if rates is None:
        return None
    a, b = rates
    grad = np.asarray(grad, np.float32)
    hess = np.asarray(hess, np.float32)
    n = len(grad)
    if rows is not None:
        grad, hess = grad[rows], hess[rows]
    cls = jax.device_get(goss_sample(
        grad, hess, iteration, top_rate=a, other_rate=b,
        bagging_seed=int(cfg.bagging_seed))[0])
    sub_mask = (cls != GOSS_OUT).astype(np.float32)
    sub_mult = np.where(cls == GOSS_REST, np.float32((1.0 - a) / max(b, 1e-12)),
                        np.float32(1.0)).astype(np.float32)
    if rows is None:
        return sub_mask, sub_mult
    mask = np.zeros(n, np.float32)
    mask[rows] = sub_mask
    mult = np.ones(n, np.float32)
    mult[rows] = sub_mult
    return mask, mult


def feature_mask_np(cfg, num_features: int,
                    iteration: int) -> Optional[np.ndarray]:
    """Per-iteration feature_fraction mask (ColSampler per-tree draw), or
    None when feature_fraction is 1.0."""
    if cfg.feature_fraction >= 1.0:
        return None
    rng = host_rng(cfg.feature_fraction_seed, iteration)
    k = max(1, int(np.ceil(num_features * cfg.feature_fraction)))
    idx = rng.choice(num_features, size=k, replace=False)
    mask = np.zeros(num_features, bool)
    mask[idx] = True
    return mask


def make_walk_fn(efb_walk, dense_ok: bool):
    """Binned tree-walk selector shared by GBDT._walk and multitrain:
    EFB-bundled datasets decode bundle columns; categorical-free datasets
    take the dense matmul walk (no per-row gathers)."""
    if efb_walk is not None:
        if dense_ok:
            def walk(bins, *tree_args):
                (sf, tb, nb, _cm, dt, lc, rc, lv, nl) = tree_args
                return _walk_binned_dense_efb(bins, efb_walk, sf, tb, nb,
                                              dt, lc, rc, lv, nl)
            return walk
        return lambda bins, *tree_args: _walk_binned_efb(bins, efb_walk,
                                                         *tree_args)
    if dense_ok:
        def walk(bins, *tree_args):
            (sf, tb, nb, _cm, dt, lc, rc, lv, nl) = tree_args
            return _walk_binned_dense(bins, sf, tb, nb, dt, lc, rc, lv, nl)
        return walk
    return _walk_binned


from .tree import (_walk_binned,  # tree walk for valid-set score updates
                   _walk_binned_dense, _walk_binned_dense_efb,
                   _walk_binned_efb)


class GBDT:
    """Boosting driver (reference include/LightGBM/boosting.h:27 ``Boosting``
    interface + src/boosting/gbdt.h:540 ``GBDT``)."""

    name = "gbdt"
    # Deferred tree materialization: grown trees stay device-side and are
    # pulled to host in one batched fetch only when the model is actually
    # read (predict/save/rollback/...).  Keeps the boosting loop fully
    # async — crucial when the accelerator link has high latency.  DART
    # needs host trees every iteration and opts out.
    _defer_trees = True

    def __init__(self, config: Config, train_set: Optional[Dataset],
                 objective: Optional[ObjectiveFunction] = None) -> None:
        self.config = config
        self._models_list: List[Tree] = []
        self._pending: List[tuple] = []
        self.train_set: Optional[Dataset] = None
        self.valid_sets: List[Tuple[str, Dataset]] = []
        self.valid_scores: List[jnp.ndarray] = []
        self.valid_metrics: List[List[Metric]] = []
        self.train_metrics: List[Metric] = []
        self.objective = objective
        self.iter_ = 0
        self.init_scores: Optional[np.ndarray] = None
        self.best_iteration = -1
        # loaded (train-set-less) models keep an inert record so the
        # eval/snapshot surfaces never need a None check; _init_train
        # replaces it with the published per-run record (same deal for
        # the flight recorder: inert/disabled until a training run)
        self.train_record = TrainRecord(meta={"boosting": self.name})
        self._first_update = False
        from ..telemetry.flight import FlightRecorder
        self.flight = FlightRecorder(capacity=1, enabled=False)
        if train_set is not None:
            self._init_train(train_set)

    # -- setup ---------------------------------------------------------------
    def _init_train(self, train_set: Dataset) -> None:
        cfg = self.config
        t_init = time.perf_counter()
        setup_s: Dict[str, float] = {}
        # params verbosity drives the global log level (reference: the C++
        # global Log level is set from config at Booster creation)
        from ..utils.log import set_verbosity
        set_verbosity(int(cfg.verbosity))
        from ..config import warn_unimplemented_params
        warn_unimplemented_params(cfg)
        train_set.construct(cfg)
        self.train_set = train_set
        self.num_data = train_set.num_data()
        self.num_features = train_set.num_feature()
        mappers = [train_set.bin_mappers[j] for j in train_set.used_feature_map]
        from ..binning import MissingType
        self.max_bins = int(max(m.num_bin for m in mappers))
        num_bins = np.array([m.num_bin for m in mappers], np.int32)
        is_cat = np.array([m.is_categorical for m in mappers], bool)
        has_nan = np.array([m.missing_type == MissingType.NAN for m in mappers],
                           bool)
        if cfg.tree_learner == "auto":
            # world-size + modeled-bytes learner selection (PV-Tree,
            # arXiv:1611.01276): voting when its modeled CROSS-HOST
            # histogram bytes per pass undercut the DP reduce-scatter
            # path's, data-parallel otherwise; single-device worlds are
            # the serial learner.  Resolved in place so every downstream
            # gate (EFB, pre_partition, shard counts, model text) sees
            # the concrete learner.
            from ..parallel.voting_parallel import voting_favored
            _world = jax.device_count()
            if _world <= 1 and jax.process_count() == 1:
                cfg.tree_learner = "serial"
            elif voting_favored(self.num_features, self.max_bins,
                                int(cfg.top_k), _world):
                cfg.tree_learner = "voting"
            else:
                cfg.tree_learner = "data"
            log_info(f"tree_learner=auto resolved to "
                     f"'{cfg.tree_learner}' (world={_world}, "
                     f"features={self.num_features}, "
                     f"top_k={int(cfg.top_k)})")
        learner_cfg = cfg
        from ..utils.backend import default_backend as _safe_backend
        _backend = _safe_backend()
        _autotune_ok = (
            cfg.tpu_histogram_impl == "auto" and
            train_set.X_binned.size <= (1 << 22) and
            self.max_bins <= 256 and
            cfg.tree_learner in ("serial", "") and
            # EFB bundles histogram in BUNDLE space (bundle_bins can
            # exceed the per-feature max) and the probe would time the
            # wrong shapes — keep the static choice there
            train_set.efb is None and
            (_backend == "tpu" or
             # CPU: the joint-nibble packed4 scatter only competes when
             # every feature fits 4-bit bins, and the probe's compiles
             # only pay off past benchmark-ish scale
             (self.max_bins <= 16 and
              train_set.X_binned.size >= (1 << 18))))
        if _autotune_ok:
            # small shapes: time the kernel variants (pallas dma /
            # blockspec / packed / onehot on TPU; segment vs packed4 on
            # CPU) on the real data once (dataset.cpp:659-670's
            # ShareStates timing analog); large shapes keep the measured
            # static choice.  Winners persist per (shape, backend) in
            # the autotune disk cache, and go to a COPY so the user's
            # 'auto' survives param round-trips.
            from ..learner.autotune import apply_winner, pick_hist_impl
            import copy as _copy
            learner_cfg = _copy.copy(cfg)
            apply_winner(learner_cfg,
                         pick_hist_impl(train_set.X_binned, self.max_bins))
        self.learner = self._create_learner(num_bins, is_cat, has_nan,
                                            self._inner_monotone(),
                                            cfg=learner_cfg)
        # dense binned walk gate: per-node categorical membership needs a
        # gather (EFB bundles decode elementwise and are fine)
        self._walk_dense_ok = not bool(np.any(is_cat))
        _shards = jax.device_count() \
            if cfg.tree_learner in ("data", "voting") else 1
        if self.num_data > (1 << 24) * _shards and \
                not cfg.use_quantized_grad:
            # f32 histogram counts are exact to 2^24 rows PER SHARD
            # (ops/histogram.py); the quantized path accumulates int32
            # counts exact to 2^31 (reference data_size_t, meta.h:28)
            log_warning(f"num_data={self.num_data} exceeds the f32 "
                        "histogram count channel's 16.7M-rows-per-shard "
                        "exactness range; set use_quantized_grad=true for "
                        "exact int32 counts (and faster training) at this "
                        "scale")
        if self._hist_acc_rows(cfg) and \
                not getattr(self.learner, "hist_acc_rows", 0):
            # the wave grower sums such a pass in segments
            # (ops/quantize.py); a learner that took another grower
            # cannot
            log_warning(
                f"num_data={self.num_data}: one bin of this data set can "
                "hold more rows than the quantized histogram's int32 sums "
                "are exact for, and this tree learner does not segment "
                "them; lower num_grad_quant_bins or shard rows across "
                "more devices")
        # bins, labels, weights and scores go to the device: host seconds of
        # ENQUEUEING the copies (device_put returns before they land)
        with timed_span(setup_s, "upload", "train/init/upload"):
            if getattr(train_set, "distributed_rows", False):
                # pre-partitioned ingest: assemble the global row-sharded
                # matrix from each process's local shard (features never
                # replicate across hosts)
                if cfg.tree_learner not in ("data", "voting"):
                    raise ValueError("pre_partition-ed training requires "
                                     "tree_learner=data or voting")
                from jax.sharding import NamedSharding, PartitionSpec as _P
                from ..parallel.mesh import get_mesh as _get_mesh
                _mesh = _get_mesh(int(cfg.num_devices))
                _ax = _mesh.axis_names[0]
                self.X_dev = jax.make_array_from_process_local_data(
                    NamedSharding(_mesh, _P(_ax)), train_set.X_binned)
                self._row_valid = jax.make_array_from_process_local_data(
                    NamedSharding(_mesh, _P(_ax)), train_set._dist_valid_local)
            else:
                # under a row-sharded learner the bin codes leave the host
                # as row shards, one per device: no whole copy on device 0
                sharding = self._mesh_record()["chips"] > 1
                with timed_span(setup_s, "shard", "train/init/shard") \
                        if sharding else contextlib.nullcontext():
                    self.X_dev = self._put_rows(train_set.X_binned)
                self._row_valid = None
            self._is_cat_np = is_cat
            # bundle-space tree-walk decode arrays (EFB valid sets / rebuilds)
            # — the standard efb_arrays layout minus exp_map (unused by the
            # walk's decode)
            efb = getattr(train_set, "efb", None)
            self._efb_walk = None if efb is None else (
                None, jnp.asarray(efb.f_bundle), jnp.asarray(efb.f_offset),
                jnp.asarray(efb.f_default), jnp.asarray(efb.f_nbins),
                jnp.asarray(efb.f_single))
            # CEGB (cost_effective_gradient_boosting.hpp): coupled per-feature
            # penalties charge once until the feature is first used; tracked
            # host-side across trees (per-tree granularity)
            self._cegb_coupled = None
            supports_extras = self.learner.supports_extras
            if cfg.cegb_penalty_feature_coupled or cfg.cegb_penalty_split > 0:
                if not supports_extras:
                    log_warning("CEGB penalties are applied by the serial and "
                                "data-parallel(wave) learners only; this "
                                "learner ignores them")
                elif cfg.cegb_penalty_feature_coupled:
                    full = np.zeros(train_set.num_total_features, np.float64)
                    cpl = cfg.cegb_penalty_feature_coupled
                    full[:len(cpl)] = [float(v) for v in cpl]
                    self._cegb_coupled = (full[train_set.used_feature_map] *
                                          float(cfg.cegb_tradeoff))
                    self._cegb_used = np.zeros(self.num_features, bool)
                    self._defer_trees = False  # used-set updates per tree
            if cfg.feature_fraction_bynode < 1.0 and not supports_extras:
                log_warning("feature_fraction_bynode is applied by the serial "
                            "and data-parallel(wave) learners only; this "
                            "learner ignores it")
            self._linear = bool(cfg.linear_tree)
            if self._linear and self.name != "gbdt":
                log_warning(f"linear_tree is not supported with "
                            f"boosting={self.name}; training plain trees")
                self._linear = False
            if self._linear:
                # linear leaves re-fit on raw values each iteration; tree
                # deferral buys nothing here
                self._defer_trees = False
                if getattr(train_set, "distributed_rows", False):
                    # pre-partitioned: assemble the row-sharded global raw
                    # matrix like X_dev (local shards never replicate)
                    from jax.sharding import NamedSharding
                    from jax.sharding import PartitionSpec as _P2
                    from ..parallel.mesh import get_mesh as _get_mesh2
                    _mesh2 = _get_mesh2(int(cfg.num_devices))
                    self.X_raw_dev = jax.make_array_from_process_local_data(
                        NamedSharding(_mesh2, _P2(_mesh2.axis_names[0])),
                        train_set.raw_used)
                else:
                    self.X_raw_dev = jnp.asarray(train_set.raw_used)

            if self.objective is None and cfg.objective != "none":
                self.objective = create_objective(cfg.objective, cfg)
            if self.objective is not None:
                self.objective.init(train_set.metadata, self.num_data)
                self.objective.place_rows(self._put_rows)
            self.num_tree_per_iteration = (
                self.objective.num_model_per_iteration if self.objective else
                max(1, cfg.num_class if cfg.num_class > 1 else 1))
            k = self.num_tree_per_iteration
            shape = (self.num_data,) if k == 1 else (self.num_data, k)

            # initial scores: user init_score > boost_from_average > zero
            self._pending_bias = np.zeros(k)
            score0 = np.zeros(shape, np.float32)
            md = train_set.metadata
            if md.init_score is not None:
                init = md.init_score.reshape(shape)
                score0 = score0 + init.astype(np.float32)
            elif cfg.boost_from_average and self.objective is not None:
                for cid in range(k):
                    s = self.objective.boost_from_score(cid)
                    self._pending_bias[cid] = s
                    if abs(s) > EPSILON:
                        log_info(f"Start training from score {s:.6f}")
                if k == 1:
                    score0 = score0 + np.float32(self._pending_bias[0])
                else:
                    score0 = score0 + self._pending_bias[None, :].astype(np.float32)
            self.score = self._put_rows(score0)

        self.train_metrics = []
        if cfg.is_provide_training_metric:
            self.train_metrics = create_metrics(cfg)
            for m in self.train_metrics:
                m.init(md, self.num_data)

        # telemetry: one TrainRecord per training run (per-tree histogram
        # passes, per-phase wall time, trace-time collective tallies,
        # compile events, device-memory watermark).  Purely observational
        # — reads values the loop already computes — and published as the
        # process's freshest record so /metrics can export it.
        self.train_record = TrainRecord(meta={
            "boosting": self.name,
            "objective": str(cfg.objective),
            "tree_learner": str(cfg.tree_learner) or "serial",
            "num_leaves": int(cfg.num_leaves),
            "num_data": int(self.num_data),
            "num_features": int(self.num_features),
        }, compile_since=t_init, mesh=self._mesh_record(),
            grower=getattr(self.learner, "grower_paths", None),
            score_update=self._choose_score_update(),
            efb=train_set.efb.record() if train_set.efb is not None
            else None,
            objective=self.objective.record() if self.objective is not None
            else None)
        self.train_record.add_setup_seconds(
            getattr(train_set, "setup_seconds", {}))
        self.train_record.add_setup_seconds(setup_s)
        set_last_train_record(self.train_record)
        self._first_update = True
        # flight recorder: bounded per-iteration event ring for crash/
        # preemption post-mortems (telemetry/flight.py).  Observation
        # only — recorder-on training is bit-identical to recorder-off.
        from ..telemetry.flight import FlightRecorder
        self.flight = FlightRecorder(
            capacity=int(cfg.flight_events),
            enabled=bool(cfg.flight_recorder),
            meta={"boosting": self.name, "objective": str(cfg.objective),
                  "num_data": int(self.num_data)})

    def _inner_monotone(self) -> Optional[np.ndarray]:
        """Map config.monotone_constraints (original column indexing, may be
        shorter than the column count) onto the inner used-feature axis."""
        mc = self.config.monotone_constraints
        if not mc or not any(int(v) != 0 for v in mc):
            return None
        ts = self.train_set
        full = np.zeros(ts.num_total_features, np.int32)
        full[:len(mc)] = [int(v) for v in mc]
        return full[ts.used_feature_map]

    def _parse_forced_splits(self) -> tuple:
        """forcedsplits_filename JSON -> BFS-ordered (leaf, inner feature,
        threshold bin) triples (reference serial_tree_learner.cpp:450
        ForceSplits + application-level json load)."""
        fn = self.config.forcedsplits_filename
        if not fn:
            return ()
        import json
        from collections import deque
        with open(fn) as fh:
            root = json.load(fh)
        ts = self.train_set
        inner_of_real = {int(r): i for i, r in enumerate(ts.used_feature_map)}
        mappers = [ts.bin_mappers[j] for j in ts.used_feature_map]
        out = []
        q = deque([(root, 0)])
        next_id = 1
        while q and len(out) < self.config.num_leaves - 1:
            node, leaf = q.popleft()
            if not node or "feature" not in node:
                continue
            rf = int(node["feature"])
            if rf not in inner_of_real:
                log_warning(f"forced split on trivial/unknown feature {rf} "
                            f"skipped (with its subtree)")
                continue
            f = inner_of_real[rf]
            b = int(mappers[f].value_to_bin(
                np.array([float(node["threshold"])]))[0])
            out.append((leaf, f, b))
            new_id = next_id
            next_id += 1
            if "left" in node:
                q.append((node["left"], leaf))
            if "right" in node:
                q.append((node["right"], new_id))
        return tuple(out)

    def _inner_cegb_lazy(self) -> tuple:
        """cegb_penalty_feature_lazy mapped to inner features, pre-scaled
        by cegb_tradeoff (like the coupled penalties)."""
        lz = self.config.cegb_penalty_feature_lazy
        if not lz:
            return ()
        full = np.zeros(self.train_set.num_total_features, np.float64)
        full[:len(lz)] = [float(v) for v in lz]
        inner = full[self.train_set.used_feature_map] * \
            float(self.config.cegb_tradeoff)
        if not np.any(inner):
            return ()  # numerically a no-op: skip the bitmap machinery
        return tuple(float(v) for v in inner)

    def _inner_contri(self) -> tuple:
        """config.feature_contri (original column indexing) -> per-inner-
        feature gain multipliers (feature_histogram.hpp:94 penalty)."""
        fc = self.config.feature_contri
        if not fc:
            return ()
        ts = self.train_set
        full = np.ones(ts.num_total_features, np.float64)
        full[:len(fc)] = [float(v) for v in fc]
        return tuple(full[ts.used_feature_map])

    def _parse_interaction_constraints(self) -> tuple:
        """config.interaction_constraints "[0,1],[2,3]" -> tuples of INNER
        feature indices (reference col_sampler.hpp constraint sets)."""
        spec = self.config.interaction_constraints
        if not spec:
            return ()
        import re
        ts = self.train_set
        inner_of_real = {int(r): i for i, r in enumerate(ts.used_feature_map)}
        groups = []
        for grp in re.findall(r"\[([^\]]*)\]", str(spec)):
            feats = [inner_of_real[int(v)] for v in grp.split(",")
                     if v.strip() and int(v) in inner_of_real]
            if feats:
                groups.append(tuple(sorted(set(feats))))
        return tuple(groups)

    def _create_learner(self, num_bins, is_cat, has_nan, monotone=None,
                        cfg=None):
        cfg = cfg if cfg is not None else self.config
        if cfg.tree_learner == "serial" or cfg.num_machines <= 1 and \
                cfg.tree_learner not in ("data", "feature", "voting"):
            return SerialTreeLearner(cfg, self.num_features, self.max_bins,
                                     num_bins, is_cat, has_nan, monotone,
                                     self._parse_forced_splits(),
                                     efb=self.train_set.efb,
                                     interaction_groups=
                                     self._parse_interaction_constraints(),
                                     feature_contri=self._inner_contri(),
                                     cegb_lazy=self._inner_cegb_lazy(),
                                     acc_rows=self._hist_acc_rows(cfg))
        from ..parallel import create_parallel_learner
        return create_parallel_learner(
            cfg, self.num_features, self.max_bins, num_bins, is_cat,
            has_nan, monotone,
            interaction_groups=self._parse_interaction_constraints(),
            cegb_lazy=self._inner_cegb_lazy(),
            forced_splits=self._parse_forced_splits(),
            feature_contri=self._inner_contri(),
            acc_rows=self._hist_acc_rows(cfg))

    def _hist_acc_rows(self, cfg) -> int:
        """Rows a quantized histogram pass may add into one int32 on this
        data set (ops/quantize.py ``hist_acc_rows``; 0: no sum can wrap),
        from the rows a shard holds, the levels and the fullest bin of
        the binning sample.  Bundled columns count as share 1: a bundle's
        default bin holds the rows that are default in ALL its members."""
        if not cfg.use_quantized_grad:
            return 0
        from ..ops.quantize import hist_acc_rows, quant_levels
        ts = self.train_set
        share = 1.0 if ts.efb is not None else max(
            (float(ts.bin_mappers[j].max_bin_share)
             for j in ts.used_feature_map), default=1.0)
        shards = jax.device_count() \
            if cfg.tree_learner in ("data", "voting") else 1
        return hist_acc_rows(-(-int(self.num_data) // shards),
                             *quant_levels(int(cfg.num_grad_quant_bins)),
                             share)

    def _mesh_record(self) -> Dict[str, Any]:
        """``TrainRecord.snapshot()["mesh"]``: the mesh the learner built
        for its row shards (one chip and no axis where rows stay whole:
        the serial and the feature-parallel learner)."""
        mesh = self.learner.mesh
        if mesh is None or not self.learner.rows_sharded:
            return {"chips": 1, "axis": None,
                    "rows_per_chip": int(self.num_data)}
        return {"chips": int(mesh.size), "axis": str(mesh.axis_names[0]),
                "rows_per_chip": -(-int(self.num_data) // int(mesh.size))}

    def _row_mesh(self, rows: int):
        """The learner's mesh where per-row arrays of ``rows`` rows live
        on it as row shards, else None: rows that do not divide over the
        mesh stay on one device, and so does everything in a
        multi-process world (each process holds the full host data there
        and reads labels and scores back, which a cross-process array
        does not allow)."""
        mesh = self.learner.mesh
        if mesh is not None and self.learner.rows_sharded \
                and jax.process_count() == 1 and rows % mesh.size == 0:
            return mesh
        return None

    def _put_rows(self, arr):
        """Host per-row array -> device.  Under a row-sharded learner
        (tree_learner=data/voting) the array is created on the learner's
        mesh, sharded by rows, so the bin matrix, scores, labels and
        masks never sit whole on device 0 to be re-scattered by every
        grower call; where :meth:`_row_mesh` has none for it, the
        learner scatters per call."""
        mesh = self._row_mesh(arr.shape[0])
        if mesh is not None:
            from ..parallel.mesh import shard_rows
            return shard_rows(mesh, arr, mesh.axis_names[0])
        return jnp.asarray(arr)

    def _choose_score_update(self) -> str:
        """Bind ``self._score_upd``, the jitted entry every tree's
        training-set score update goes through (donated on the TPU), and
        say which lowering it is
        (``TrainRecord.snapshot()["score_update"]``)."""
        from ..utils.backend import default_backend
        backend = default_backend()
        lowering = score_update_lowering(backend,
                                         int(self.config.num_leaves))
        mesh = self._row_mesh(self.num_data)
        if mesh is None and self.learner.mesh is not None \
                and self.learner.rows_sharded:
            # score and row_leaf are not shards of one mesh: the gather,
            # which GSPMD partitions however they lie
            lowering = "gather"
        if lowering == "select":
            self._score_upd = _update_score_by_select_donated \
                if mesh is None else \
                _update_score_by_select_sharded(mesh, mesh.axis_names[0])
        else:
            self._score_upd = _update_score_by_leaf_donated \
                if backend == "tpu" else _update_score_by_leaf
        return lowering

    def _walk(self, bins, *tree_args):
        """Binned tree walk; routes through the bundle-space decode
        when the dataset is EFB-bundled (valid sets aligned to an EFB
        reference carry BUNDLE columns).  Categorical-free non-EFB
        datasets take the dense matmul walk (no per-row gathers)."""
        return make_walk_fn(self._efb_walk,
                            getattr(self, "_walk_dense_ok", False))(
            bins, *tree_args)

    def add_valid(self, valid_set: Dataset, name: str) -> None:
        # a valid set must share the train set's bin mappers (and bundle
        # layout under EFB) — the binned walk reads TRAIN-space codes
        # (reference dataset.h:304 alignment check raises the same way)
        if valid_set is not self.train_set and \
                getattr(valid_set, "reference", None) is not self.train_set \
                and not valid_set.constructed:
            valid_set.reference = self.train_set
        valid_set.construct(self.config)
        if getattr(self, "_row_valid", None) is not None and \
                valid_set is not self.train_set:
            # pre_partition training evaluates valid metrics per process
            # with NO cross-process reduction; every rank must therefore
            # hold the SAME (replicated) validation data, or metric-driven
            # decisions (early stopping) would diverge and desync the
            # collectives.  Checked by label checksum across ranks.
            from .. import distributed as _dist
            lab = valid_set.metadata.label
            sig = np.asarray([0.0 if lab is None else float(lab.sum()),
                              0.0 if lab is None else float(len(lab))],
                             np.float64)
            sigs = _dist.allgather_host(sig).reshape(-1, 2)
            if not np.allclose(sigs, sigs[0]):
                raise ValueError(
                    "under pre_partition every process must pass the SAME "
                    "validation data (metrics are evaluated per process); "
                    "got differing label checksums across ranks")
        if valid_set is not self.train_set and \
                valid_set.bin_mappers is not self.train_set.bin_mappers and \
                not _mappers_equal(valid_set.bin_mappers,
                                   self.train_set.bin_mappers):
            raise ValueError(
                "cannot add validation data: it was constructed without "
                "reference to the training Dataset (different bin "
                "mappers); pass reference=train_set when creating it")
        if valid_set.num_feature() != self.num_features:
            raise ValueError("validation set feature count differs from train")
        k = self.num_tree_per_iteration
        n = valid_set.num_data()
        shape = (n,) if k == 1 else (n, k)
        score0 = np.zeros(shape, np.float32)
        if valid_set.metadata.init_score is not None:
            score0 = score0 + valid_set.metadata.init_score.reshape(shape).astype(
                np.float32)
        elif self.config.boost_from_average and self.objective is not None:
            score0 = score0 + (np.float32(self._pending_bias[0]) if k == 1 else
                               self._pending_bias[None, :].astype(np.float32))
        metrics = create_metrics(self.config)
        for m in metrics:
            m.init(valid_set.metadata, n)
        self.valid_sets.append((name, valid_set))
        vscore = jnp.asarray(score0)
        valid_set._device_cache["bins"] = jnp.asarray(valid_set.X_binned)
        if self.models:  # continued training: include loaded trees' scores
            vbins = valid_set._device_cache["bins"]
            for t, tree in enumerate(self.models):
                cid = t % k
                delta = self._walk(
                    vbins, jnp.asarray(tree.split_feature),
                    jnp.asarray(tree.threshold_bin), jnp.asarray(tree.nan_bin),
                    _tree_cat_member(tree),
                    jnp.asarray(tree.decision_type.astype(np.int32)),
                    jnp.asarray(tree.left_child), jnp.asarray(tree.right_child),
                    jnp.asarray(tree.leaf_value, dtype=jnp.float32),
                    jnp.asarray(tree.num_leaves, dtype=jnp.int32))
                vscore = (vscore + delta if k == 1
                          else vscore.at[:, cid].add(delta))
        self.valid_scores.append(vscore)
        self.valid_metrics.append(metrics)

    # -- sampling (bagging / GOSS hooks) -------------------------------------
    def _prepare_iter_sampling(self, grad: jnp.ndarray, hess: jnp.ndarray
                               ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray, Any]:
        """Per-iteration row sampling: returns (grad, hess, mask, rows in
        the bag: a host int or a device scalar).  Base GBDT implements
        bagging (gbdt.cpp:228 Bagging, resampled every bagging_freq
        iters); GOSS overrides."""
        cfg = self.config
        n = self.num_data
        label = (np.asarray(self.train_set.metadata.label)
                 if cfg.objective == "binary" and
                 self.train_set.metadata.label is not None else None)
        mask = bagging_mask_np(cfg, n, self.iter_, label=label)
        if mask is None:
            self._bag_mask = self._all_rows_mask()
            return grad, hess, self._bag_mask, n
        self._bag_mask = jnp.asarray(mask)
        return grad, hess, self._bag_mask, int(mask.sum())

    def _all_rows_mask(self) -> jnp.ndarray:
        """The mask of a tree that sees every row: ones, placed once as
        ``_put_rows`` places per-row arrays, and kept."""
        if getattr(self, "_ones_mask", None) is None or \
                self._ones_mask.shape[0] != self.num_data:
            self._ones_mask = self._put_rows(
                np.ones(self.num_data, np.float32))
        return self._ones_mask

    def last_sample(self) -> Optional[jnp.ndarray]:
        """Read-only: the per-row classes of the newest iteration's GOSS
        draw (uint8 device array: ``GOSS_OUT`` / ``GOSS_TOP`` /
        ``GOSS_REST``), or None where that iteration drew no sample."""
        return getattr(self, "_last_sample", None)

    def _feature_mask(self) -> Optional[jnp.ndarray]:
        mask = feature_mask_np(self.config, self.num_features, self.iter_)
        return None if mask is None else jnp.asarray(mask)

    # -- one boosting iteration (gbdt.cpp:369 TrainOneIter) ------------------
    def train_one_iter(self, grad: Optional[jnp.ndarray] = None,
                       hess: Optional[jnp.ndarray] = None) -> bool:
        """One boosting iteration under a ``train/iter`` span.  The run's
        first is ``train/first_update`` instead and its host seconds go to
        ``setup_seconds["first_update"]``: it traces, lowers and compiles
        (or loads) every program of a tree and lays out the bin matrix,
        then, like every later one, only ENQUEUES the tree."""
        rec = self.train_record
        if self._first_update:
            self._first_update = False
            with rec.setup("first_update", "train/first_update"):
                finished = self._train_one_iter(grad, hess)
            rec.add_setup_seconds(self.learner.setup_seconds)
        else:
            with span("train/iter"):
                finished = self._train_one_iter(grad, hess)
        rec.end_of_update()
        return finished

    def _train_one_iter(self, grad, hess) -> bool:
        cfg = self.config
        k = self.num_tree_per_iteration
        rec = self.train_record
        if grad is None or hess is None:
            if self.objective is None:
                raise ValueError("no objective: pass gradients explicitly "
                                 "(custom objective path, boosting.h:85)")
            with rec.phase("gradients"):
                grad, hess = self.objective.get_gradients(self.score)
        else:
            def _coerce(a):
                a = jnp.asarray(a, jnp.float32)
                if k == 1:
                    return a.reshape((self.num_data,))
                if a.ndim == 2:
                    if a.shape == (self.num_data, k):
                        return a
                    if a.shape == (k, self.num_data):
                        return a.T
                    raise ValueError(
                        f"custom objective gradients have shape {a.shape}; "
                        f"expected ({self.num_data}, {k}) or flat "
                        f"class-major length {self.num_data * k}")
                # flat custom-fobj output is CLASS-MAJOR in the reference
                # API (grouped by class_id then row_id, c_api.cpp
                # UpdateOneIterCustom convention)
                return a.reshape((k, self.num_data)).T
            grad = _coerce(grad)
            hess = _coerce(hess)

        # Lagged no-split stop for the deferred-tree path: the one place
        # a boosting iteration waits for the device.  The pull returns
        # when the PREVIOUS iteration's tree sizes are computed, i.e. when
        # its grower is done (its score update may still run), so the host
        # stays a tree ahead; the clock read behind it is that tree's
        # completion stamp, ``wait_prev`` the host's wait for the device.
        # When the previous iteration grew only stumps, pop them (the
        # reference pops non-splitting trees, gbdt.cpp:430-450) and stop.
        prev = getattr(self, "_prev_iter_leaves", None)
        if prev is not None:
            with rec.phase("wait_prev"):
                prev = jax.device_get(prev)
            rec.tree_done(self.iter_ - 1)
            if all(int(x) <= 1 for x in prev):
                self._prev_iter_leaves = None
                self._pop_stump_iteration()
                log_warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                return True

        finished = True
        # flight-event fields (last class)
        fl_passes = fl_leaves = fl_gain = None
        fmask = self._feature_mask()
        with rec.phase("sample"):
            # sampled_rows is fetched with the tree's other counters
            grad, hess, mask, sampled_rows = \
                self._prepare_iter_sampling(grad, hess)
        if getattr(self, "_row_valid", None) is not None:
            # pre_partition padding rows never enter a tree (applied
            # centrally so GOSS's override is covered too)
            mask = mask * self._row_valid
        self._last_sample_mask = mask
        leaves_this_iter = []
        for cid in range(k):
            g = grad if k == 1 else grad[:, cid]
            h = hess if k == 1 else hess[:, cid]
            self._cur_gh = (g, h)
            extra = {}
            it = self.iter_ * k + cid
            if self.learner.supports_extras:
                if self._cegb_coupled is not None:
                    extra["cegb_penalty"] = jnp.asarray(
                        np.where(self._cegb_used, 0.0,
                                 self._cegb_coupled), jnp.float32)
                if cfg.feature_fraction_bynode < 1.0 or cfg.extra_trees:
                    # independent streams, like the reference's separate
                    # ColSampler and ExtraTrees RNGs: row 0 = bynode
                    # sampling (feature_fraction_seed), row 1 =
                    # ExtraTrees thresholds (extra_seed)
                    extra["node_key"] = jnp.stack([
                        jax.random.fold_in(jax.random.PRNGKey(
                            cfg.feature_fraction_seed), it),
                        jax.random.fold_in(jax.random.PRNGKey(
                            cfg.extra_seed), it)])
            if self.learner.quantized:
                # per-tree stochastic-rounding stream
                # (gradient_discretizer.cpp seeds from config seed)
                extra["quant_key"] = jax.random.fold_in(
                    jax.random.PRNGKey(cfg.seed), it)
            with rec.phase("grow"):
                try:
                    grown = self.learner.train(self.X_dev, g, h, mask,
                                               feature_mask=fmask,
                                               **extra)
                except Exception as exc:
                    _name_refused_kernels(exc)
                    raise
            rec.add_tree(self.iter_, cid, grown.hist_passes,
                         grown.num_leaves, grown.wave_passes,
                         grown.endgame_passes, grown.ramp_committed,
                         grown.hist_rows_contracted, sampled_rows,
                         grown.decision_type, grown.pass_log,
                         grown.ramp_sample)
            if self.flight.enabled:
                # last grown tree's fields for this iteration's
                # flight event (device scalars, pulled lazily on
                # dump; the max over split gains is one tiny
                # device reduce)
                fl_passes = grown.hist_passes
                fl_leaves = grown.num_leaves
                fl_gain = jnp.max(grown.split_gain)
            with rec.phase("record"):
                tree = self._record_tree(grown, cid)
            if tree is not None and self._cegb_coupled is not None:
                sf = tree.split_feature[:tree.num_leaves - 1]
                self._cegb_used[sf[sf >= 0]] = True
            if tree is None:
                # deferred: the lagged check above decides next iteration
                finished = False
                leaves_this_iter.append(grown.num_leaves)
            elif tree.num_leaves > 1:
                finished = False
        self._prev_iter_leaves = leaves_this_iter or None
        for x in leaves_this_iter:
            # start the device->host copy NOW so next iteration's
            # lagged stump check reads a landed value instead of
            # paying a blocking ~100 ms round trip per iteration
            # (small-shape configs spend more time in that RTT than
            # in their kernels)
            if hasattr(x, "copy_to_host_async"):
                x.copy_to_host_async()
        rec.end_of_iter(self.iter_)
        self.iter_ += 1
        if self.flight.enabled:
            self.flight.note_iter(
                self.iter_, hist_passes=fl_passes,
                num_leaves=fl_leaves, best_gain=fl_gain)
        if self.iter_ % 16 == 1:
            # periodic device-memory watermark sample (cheap local
            # PJRT query; None on backends without memory_stats)
            rec.note_memory()
        if finished:
            log_warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
        return finished

    def _pop_stump_iteration(self) -> None:
        """Drop the previous iteration's no-split stump trees (they carry a
        near-zero constant; their score nudge is left in place — training is
        over and prediction reads only the model list).  The FIRST
        iteration's trees are kept even when they are stumps: they carry the
        boost-from-average constant (reference gbdt.cpp:443-450 pops only
        when models_.size() > num_tree_per_iteration)."""
        k = self.num_tree_per_iteration
        if len(self._models_list) + len(self._pending) <= k:
            return
        for _ in range(k):
            if self._pending:
                self._pending.pop()
            elif self._models_list:
                self._models_list.pop()
        self.iter_ = max(0, self.iter_ - 1)

    def _current_shrinkage(self) -> float:
        """Per-iteration shrinkage; DART overrides with lr/(1+k_dropped)."""
        return float(self.config.learning_rate)

    def _renew_leaf_values(self, grown: GrownTree,
                           class_id: int) -> Optional[np.ndarray]:
        """Percentile leaf refit for L1/quantile/MAPE (reference
        serial_tree_learner.cpp:684 RenewTreeOutput +
        regression_objective.hpp RenewTreeOutput): each leaf's value becomes
        the weighted alpha-percentile of the residuals of its (in-bag)
        rows."""
        obj = self.objective
        if obj is None or not getattr(obj, "is_renew_tree_output", False):
            return None
        from ..objective.base import weighted_percentile
        alpha = float(getattr(obj, "renew_alpha", 0.5))
        row_leaf = np.asarray(grown.row_leaf)
        score = np.asarray(self.score if self.num_tree_per_iteration == 1
                           else self.score[:, class_id])
        label = np.asarray(self.train_set.metadata.label)
        resid = label - score
        w = getattr(obj, "label_weight", None)  # MAPE folds weights here
        if w is not None:
            w = np.asarray(w)
        elif self.train_set.metadata.weight is not None:
            w = np.asarray(self.train_set.metadata.weight)
        mask = np.asarray(self._last_sample_mask) > 0 \
            if getattr(self, "_last_sample_mask", None) is not None else \
            np.ones(len(label), bool)
        out = np.asarray(grown.leaf_value, np.float64).copy()
        for leaf in range(int(grown.num_leaves)):
            sel = (row_leaf == leaf) & mask
            if sel.any():
                out[leaf] = weighted_percentile(
                    resid[sel], None if w is None else w[sel], alpha)
        return out

    @property
    def models(self) -> List[Tree]:
        """Host-side tree list; materializes any pending device trees."""
        self._flush_trees()
        return self._models_list

    @models.setter
    def models(self, value: List[Tree]) -> None:
        self._pending = []
        self._models_list = value

    def _flush_trees(self) -> None:
        if not self._pending:
            return
        pend, self._pending = self._pending, []
        host_grown = jax.device_get([p[0] for p in pend])  # one batched pull
        for (_, shrinkage, bias), grown in zip(pend, host_grown):
            tree = _grown_to_tree(grown, shrinkage, self.train_set)
            if abs(bias) > EPSILON:
                tree.add_bias(bias)
            self._models_list.append(tree)

    def _record_tree(self, grown: GrownTree, class_id: int) -> Optional[Tree]:
        if getattr(self, "_linear", False):
            return self._record_tree_linear(grown, class_id)
        shrinkage = self._current_shrinkage()
        renewed = None
        defer = self._defer_trees and not (
            self.objective is not None and
            getattr(self.objective, "is_renew_tree_output", False))
        if not defer:
            renewed = self._renew_leaf_values(grown, class_id)
        bias = self._pending_bias[class_id] if self.iter_ == 0 else 0.0
        if defer:
            # keep only what _grown_to_tree reads: dropping row_leaf
            # releases the (N,) per-tree assignment (42 MB/tree at Higgs
            # scale) instead of holding it in HBM until flush and hauling
            # it through the device->host pull (the row-sharded fields:
            # in a multi-process world no process can pull them; the
            # record has taken the counters)
            self._pending.append(
                (grown._replace(
                    row_leaf=jnp.zeros((0,), jnp.int32),
                    hist_rows_contracted=np.zeros((0, 2), np.int32),
                    pass_log=np.zeros((0, 0, 6), np.int32),
                    ramp_sample=np.zeros((0, 2), np.int32)),
                 shrinkage, bias))
            tree = None
        else:
            tree = _grown_to_tree(grown, shrinkage, self.train_set,
                                  leaf_value_override=renewed)
            # fold init score into the first iteration's trees
            # (gbdt.cpp:414-427)
            if abs(bias) > EPSILON:
                tree.add_bias(bias)
            self._flush_trees()
            self._models_list.append(tree)

        # update train scores from the grower's leaf assignment
        lv = (grown.leaf_value if renewed is None
              else jnp.asarray(renewed, jnp.float32)) * shrinkage
        upd = self._score_upd
        if self.num_tree_per_iteration == 1:
            self.score = upd(self.score, grown.row_leaf, lv, 1.0)
        else:
            col = upd(self.score[:, class_id], grown.row_leaf, lv, 1.0)
            self.score = self.score.at[:, class_id].set(col)
        # update validation scores with a tree walk on their binned matrices
        for vi, (_, vset) in enumerate(self.valid_sets):
            vbins = vset._device_cache["bins"]
            delta = self._walk(vbins, grown.split_feature, grown.threshold_bin,
                                 grown.nan_bin, grown.cat_member,
                                 grown.decision_type,
                                 grown.left_child, grown.right_child,
                                 lv, grown.num_leaves)
            if self.num_tree_per_iteration == 1:
                self.valid_scores[vi] = self.valid_scores[vi] + delta
            else:
                self.valid_scores[vi] = self.valid_scores[vi].at[:, class_id].add(delta)
        return tree

    def _linear_device_arrays(self, tree: Tree):
        """Pad the tree's per-leaf linear models into device arrays for
        vectorized evaluation."""
        L = tree.max_leaves
        feats = tree.leaf_features_inner
        K = max(1, max((len(f) for f in feats), default=1))
        lf = np.zeros((L, K), np.int32)
        fm = np.zeros((L, K), np.float32)
        co = np.zeros((L, K), np.float32)
        for i, (fs, cs) in enumerate(zip(feats, tree.leaf_coeff)):
            lf[i, :len(fs)] = fs
            fm[i, :len(fs)] = 1.0
            co[i, :len(cs)] = cs
        return (jnp.asarray(lf), jnp.asarray(fm), jnp.asarray(co),
                jnp.asarray(tree.leaf_const, jnp.float32),
                jnp.asarray(tree.leaf_value, jnp.float32))

    def _record_tree_linear(self, grown: GrownTree, class_id: int
                            ) -> Optional[Tree]:
        """Linear-tree variant of _record_tree: fit per-leaf linear models
        on the raw branch features (learner/linear.py) before recording."""
        from ..learner.linear import fit_linear_leaves, linear_score_delta
        cfg = self.config
        shrinkage = self._current_shrinkage()
        g, h = self._cur_gh
        mask = self._last_sample_mask
        sf, lc, rc, nl, lv = jax.device_get(
            (grown.split_feature, grown.left_child, grown.right_child,
             grown.num_leaves, grown.leaf_value))
        feats_i, coefs, const = fit_linear_leaves(
            self.X_raw_dev, g, h, mask, grown.row_leaf, sf, lc, rc,
            max(int(nl), 1), self._is_cat_np, float(cfg.linear_lambda), lv)
        tree = _grown_to_tree(grown, 1.0, self.train_set)
        real_map, _, _ = self.feature_mapping()
        tree.is_linear = True
        tree.leaf_const = np.asarray(const, np.float64)
        tree.leaf_coeff = coefs
        tree.leaf_features_inner = feats_i
        tree.leaf_features = [[int(real_map[f]) for f in fs]
                              for fs in feats_i]
        if shrinkage != 1.0:
            tree.shrink(shrinkage)
        # device score update with POST-shrink, PRE-bias values (scores
        # already carry the boost-from-average bias)
        lf, fm, co, lconst, lval = self._linear_device_arrays(tree)
        delta = linear_score_delta(self.X_raw_dev, grown.row_leaf, lf, fm,
                                   co, lconst, lval, 1.0)
        if self.num_tree_per_iteration == 1:
            self.score = self.score + delta
        else:
            self.score = self.score.at[:, class_id].add(delta)
        for vi, (_, vset) in enumerate(self.valid_sets):
            vbins = vset._device_cache["bins"]
            idx_f = self._walk(
                vbins, grown.split_feature, grown.threshold_bin,
                grown.nan_bin, grown.cat_member, grown.decision_type,
                grown.left_child, grown.right_child,
                jnp.arange(tree.max_leaves, dtype=jnp.float32),
                grown.num_leaves)
            vleaf = idx_f.astype(jnp.int32)
            vraw = vset._device_cache.get("raw")
            if vraw is None:
                vraw = jnp.asarray(vset.raw_used)
                vset._device_cache["raw"] = vraw
            vdelta = linear_score_delta(vraw, vleaf, lf, fm, co, lconst,
                                        lval, 1.0)
            if self.num_tree_per_iteration == 1:
                self.valid_scores[vi] = self.valid_scores[vi] + vdelta
            else:
                self.valid_scores[vi] = \
                    self.valid_scores[vi].at[:, class_id].add(vdelta)
        bias = self._pending_bias[class_id] if self.iter_ == 0 else 0.0
        if abs(bias) > EPSILON:
            tree.add_bias(bias)
        self._flush_trees()
        self._models_list.append(tree)
        return tree

    # -- evaluation (gbdt.cpp:472 EvalAndCheckEarlyStopping) -----------------
    def eval_train(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        if not self.train_metrics:
            return out
        with self.train_record.phase("eval"):
            score = np.asarray(self.score)
            for m in self.train_metrics:
                for name, val, hib in m.eval(score):
                    out.append(("training", name, val, hib))
        return out

    def eval_valid(self) -> List[Tuple[str, str, float, bool]]:
        out = []
        if not self.valid_sets:
            return out
        with self.train_record.phase("eval"):
            for vi, (vname, _) in enumerate(self.valid_sets):
                score = np.asarray(self.valid_scores[vi])
                for m in self.valid_metrics[vi]:
                    for name, val, hib in m.eval(score):
                        out.append((vname, name, val, hib))
        return out

    # -- prediction ----------------------------------------------------------
    def _tree_batch(self, start: int = 0, num_iteration: Optional[int] = None
                    ) -> Optional[TreeBatch]:
        if not self.models:
            return None
        k = self.num_tree_per_iteration
        end = len(self.models) if num_iteration is None else min(
            len(self.models), (start + num_iteration) * k)
        cache = getattr(self, "_predict_cache", None)
        key = (start * k, end)
        if cache is not None and key in cache:
            return cache[key]
        trees = self.models[start * k:end]
        batch = TreeBatch(trees) if trees else None
        if cache is not None:
            # per-predict-call memo only (set up by the chunk loop); the
            # model is immutable across one call's chunks, so no
            # invalidation hazard
            cache[key] = batch
        return batch

    def _dense_program(self, t0: int, t1: int, num_features: int):
        """The serving compiler's fused program for trees [t0, t1), or
        None when the walk serves this call (mode/cost-model/lowering —
        the reason is recorded by serve/compiler.py, never silent).
        Memoized in the per-call ``_predict_cache`` so chunked predicts
        lower once."""
        cache = getattr(self, "_predict_cache", None)
        ck = ("dense", t0, t1)
        if cache is not None and ck in cache:
            return cache[ck]
        from ..serve.compiler import compile_ensemble
        cfg = self.config
        k = self.num_tree_per_iteration
        full = t0 == 0 and t1 == len(self.models)
        dense, _reason = compile_ensemble(
            self.models[t0:t1], k, num_features,
            class_ids=[t % k for t in range(t0, t1)],
            mode=getattr(cfg, "tpu_predict_compiler", "auto"),
            leaf_bits=int(getattr(cfg, "tpu_predict_leaf_bits", 0)),
            shard=int(getattr(cfg, "tpu_predict_shard", 0)),
            batch=self._tree_batch() if full else None)
        if cache is not None:
            cache[ck] = dense
        return dense

    def _explain_program(self, t0: int, t1: int, num_features: int):
        """The explain compiler's dense TreeSHAP program for trees
        [t0, t1), or None when the host walk serves this call
        (mode/budget — the reason is recorded by explain/compiler.py,
        never silent).  Memoized in the per-call ``_predict_cache`` so
        chunked contrib predicts lower once."""
        cache = getattr(self, "_predict_cache", None)
        ck = ("explain", t0, t1)
        if cache is not None and ck in cache:
            return cache[ck]
        from ..explain.compiler import compile_explain
        k = self.num_tree_per_iteration
        full = t0 == 0 and t1 == len(self.models)
        exe, _reason = compile_explain(
            self.models[t0:t1], k, num_features,
            class_ids=[t % k for t in range(t0, t1)],
            mode=getattr(self.config, "tpu_explain_compiler", "auto"),
            num_cols=self.num_features + 1,
            batch=self._tree_batch() if full else None)
        if cache is not None:
            cache[ck] = exe
        return exe

    def _predict_contrib(self, Xi, start_iteration, num_iteration):
        """SHAP contributions, routed through tpu_explain_compiler: the
        dense TreeSHAP program when it lowers, else the host walk —
        both respect the iteration window, and the dense result is
        additivity-checked (a failed invariant falls back WITH a
        recorded reason, like every other fallback)."""
        from .shap import predict_contrib, trees_window
        t0, t1 = trees_window(self, start_iteration, num_iteration)
        exe = self._explain_program(t0, t1, Xi.shape[1]) if t1 > t0 else None
        if exe is not None:
            from ..explain.compiler import (ExplainAdditivityError,
                                            note_explain_fallback_batch)
            if any(t.is_linear for t in self.models[t0:t1]):
                from ..utils.log import log_warning
                log_warning("pred_contrib on linear trees attributes each "
                            "leaf's PLAIN output (per-leaf linear terms "
                            "are not decomposed)")
            try:
                return exe.explain(Xi)
            except ExplainAdditivityError:
                note_explain_fallback_batch("additivity", "")
        return predict_contrib(self, Xi, start_iteration, num_iteration)

    def predict(self, X: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                pred_leaf: bool = False, pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: Optional[int] = None,
                pred_early_stop_margin: Optional[float] = None) -> np.ndarray:
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        # per-call memo for TreeBatch/dense-program builds: ONE build per
        # predict() call whether the call chunks or not (the dense route
        # consults the TreeBatch twice — cost model + lowering — so an
        # unmemoized call would build it twice)
        own_cache = getattr(self, "_predict_cache", None) is None
        if own_cache:
            self._predict_cache = {}
        try:
            return self._predict_cached(
                X, raw_score, start_iteration, num_iteration, pred_leaf,
                pred_contrib, pred_early_stop, pred_early_stop_freq,
                pred_early_stop_margin)
        finally:
            if own_cache:
                self._predict_cache = None

    def _predict_cached(self, X, raw_score, start_iteration, num_iteration,
                        pred_leaf, pred_contrib, pred_early_stop,
                        pred_early_stop_freq, pred_early_stop_margin
                        ) -> np.ndarray:
        # bound the device working set: very large batches walk in row
        # chunks (the reference predicts row blocks too,
        # gbdt_prediction.cpp).  The dense walk's temporaries scale with
        # rows x num_leaves, so the chunk shrinks for wide models; the
        # TreeBatch is built once per outer call, not per chunk.
        chunk = min(1 << 20,
                    max(1 << 14, (1 << 28) //
                        max(int(self.config.num_leaves), 256)))
        k = self.num_tree_per_iteration
        if X.shape[0] > chunk:
            parts = [self._predict_cached(
                X[lo:lo + chunk], raw_score, start_iteration,
                num_iteration, pred_leaf, pred_contrib, pred_early_stop,
                pred_early_stop_freq, pred_early_stop_margin)
                for lo in range(0, X.shape[0], chunk)]
            return np.concatenate(parts, axis=0)
        # map raw columns to inner (used) features
        used = self.train_set.used_feature_map if self.train_set is not None \
            else np.arange(X.shape[1])
        # every column used (the map is strictly ascending): no copy
        Xi = X if len(used) == X.shape[1] else X[:, used]
        if pred_leaf:
            return self._predict_leaf(Xi, start_iteration, num_iteration)
        if pred_contrib:
            return self._predict_contrib(Xi, start_iteration, num_iteration)
        if pred_early_stop or self.config.pred_early_stop:
            out = self._predict_early_stop(
                Xi, start_iteration, num_iteration,
                pred_early_stop_freq or self.config.pred_early_stop_freq,
                pred_early_stop_margin if pred_early_stop_margin is not None
                else self.config.pred_early_stop_margin)
            if out is not None:
                if raw_score or self.objective is None:
                    return out[:, 0] if k == 1 else out
                conv = self.objective.convert_output(
                    jnp.asarray(out if k > 1 else out[:, 0]))
                return np.asarray(conv)
        batch = self._tree_batch()
        if batch is None:
            raw = np.zeros((X.shape[0], k), np.float32)
        else:
            t0 = start_iteration * k
            t1 = batch.num_trees if num_iteration is None else min(
                batch.num_trees, (start_iteration + num_iteration) * k)
            # rows pad up the shape-bucket ladder so repeated odd-sized
            # predict calls reuse a few compiled programs instead of
            # tracing per novel row count (padding rows are sliced away
            # and cannot perturb real rows: every walk reduces per row)
            n_rows = Xi.shape[0]
            Xd = jnp.asarray(pad_rows(Xi))
            dense = self._dense_program(t0, t1, Xi.shape[1])
            if dense is not None:
                # the inference compiler's fused loop-free program:
                # every class's trees in one dense contraction set
                raw = np.asarray(dense.predict_raw(Xd))[:n_rows]
            elif k == 1:
                raw = np.asarray(
                    predict_raw(batch, Xd, t0, t1 - t0))[:n_rows, None]
            else:
                # class c's trees are at indices i*k + c
                cols = []
                cache = getattr(self, "_predict_cache", None)
                for c in range(k):
                    sel = [t for t in range(t0, t1) if t % k == c]
                    ck = ("mc", c, t0, t1)
                    if cache is not None and ck in cache:
                        sub = cache[ck]
                    else:
                        sub = TreeBatch([self.models[t] for t in sel]) \
                            if sel else None
                        if cache is not None:
                            cache[ck] = sub
                    cols.append(np.asarray(predict_raw(sub, Xd))[:n_rows]
                                if sub is not None
                                else np.zeros(n_rows, np.float32))
                raw = np.stack(cols, axis=1)
        if raw_score or self.objective is None:
            return raw[:, 0] if k == 1 else raw
        out = self.objective.convert_output(jnp.asarray(raw if k > 1 else raw[:, 0]))
        return np.asarray(out)

    def _predict_early_stop(self, Xi, start_iteration, num_iteration,
                            freq, margin):
        """Margin-based prediction early stop (prediction_early_stop.cpp):
        binary and multiclass only; None when not applicable."""
        from .tree import predict_raw_early_stop
        k = self.num_tree_per_iteration
        obj = self.config.objective
        if k > 1:
            mode = "multiclass"
        elif obj == "binary":
            mode = "binary"
        else:
            log_warning("pred_early_stop applies to binary/multiclass "
                        "objectives only; predicting normally")
            return None
        batch = self._tree_batch()
        if batch is None:
            return np.zeros((Xi.shape[0], k), np.float32)
        if batch.has_linear:
            log_warning("pred_early_stop is not supported with linear "
                        "trees; predicting normally")
            return None
        t0 = start_iteration * k
        t1 = batch.num_trees if num_iteration is None else min(
            batch.num_trees, (start_iteration + num_iteration) * k)
        base = (batch.split_feature, batch.threshold, batch.cat_words,
                batch.decision_type, batch.left_child, batch.right_child,
                batch.leaf_value, batch.num_leaves)
        per_class = tuple(tuple(a[t0 + c:t1:k] for a in base)
                          for c in range(k))
        Xp = pad_rows(np.asarray(Xi))
        # padding rows start pre-stopped: they must not keep the tree
        # loop alive after every real row has hit its margin
        stopped0 = jnp.asarray(np.arange(Xp.shape[0]) >= Xi.shape[0])
        out = predict_raw_early_stop(per_class, jnp.asarray(Xp),
                                     float(margin), stopped0,
                                     freq=max(1, int(freq)), mode=mode)
        return np.asarray(out)[:Xi.shape[0]]

    def _predict_leaf(self, Xi, start_iteration, num_iteration):
        k = self.num_tree_per_iteration
        t0 = start_iteration * k
        t1 = len(self.models) if num_iteration is None else min(
            len(self.models), (start_iteration + num_iteration) * k)
        Xd = jnp.asarray(pad_rows(np.asarray(Xi)))
        if t1 > t0:
            # pred-leaf rides the same compiled dense program (the hit
            # one-hot's argmax IS the leaf index)
            dense = self._dense_program(t0, t1, Xi.shape[1])
            if dense is not None:
                return np.asarray(dense.predict_leaf(Xd))[:Xi.shape[0]]
        leaves = []
        for t in range(t0, t1):
            tree = self.models[t]
            # walk returning leaf index: reuse raw walk on leaf-index values
            idx_tree = Tree(**{**tree.__dict__})
            idx_tree.leaf_value = np.arange(tree.max_leaves, dtype=np.float64)
            idx_tree.is_linear = False  # leaf INDEX lookup, not outputs
            tb = TreeBatch([idx_tree])
            leaves.append(np.asarray(predict_raw(tb, Xd))
                          [:Xi.shape[0]].astype(np.int32))
        return np.stack(leaves, axis=1) if leaves else np.zeros(
            (Xi.shape[0], 0), np.int32)

    # -- continued training / refit (reference gbdt.cpp:285 RefitTree;
    #    CreateBoosting(type, filename) boosting.cpp:35-67; CLI input_model
    #    path application.cpp:87-96) --------------------------------------
    def _align_loaded_tree(self, tree: Tree) -> Tree:
        """Re-key a loaded tree (REAL feature indices, raw thresholds, no bin
        info) onto this training Dataset: inner feature indices plus
        threshold_bin/nan_bin recovered through the BinMappers so the binned
        device walks work.  Exact when the data/binning match the one the
        model was trained on (the continued-training contract)."""
        ds = self.train_set
        inner_of_real = {int(r): i for i, r in enumerate(ds.used_feature_map)}
        t = Tree(**{**tree.__dict__})
        t.split_feature = np.array(tree.split_feature, np.int32, copy=True)
        t.threshold_bin = np.zeros_like(t.split_feature)
        t.nan_bin = np.full_like(t.split_feature, -1)
        from ..binning import MissingType
        from .tree import CAT_MASK as _CM
        n_int = max(t.num_leaves - 1, 1)
        member_bins = None
        for i in range(t.num_leaves - 1):
            rf = int(tree.split_feature[i])
            if rf not in inner_of_real:
                raise ValueError(
                    f"loaded model splits on feature {rf}, which is trivial "
                    f"(constant) in the continued-training dataset")
            f = inner_of_real[rf]
            t.split_feature[i] = f
            m = ds.bin_mappers[int(ds.used_feature_map[f])]
            if m.is_categorical:
                # recover the category SET (bitset over raw values) as
                # binned membership for the training-time walks
                if member_bins is None:
                    member_bins = np.zeros((n_int, self.max_bins), bool)
                if tree.cat_boundaries is not None:
                    rank = int(tree.threshold[i])
                    lo = int(tree.cat_boundaries[rank])
                    hi = int(tree.cat_boundaries[rank + 1])
                    cats = [w * 32 + b
                            for w in range(hi - lo)
                            for b in range(32)
                            if int(tree.cat_threshold[lo + w]) & (1 << b)]
                else:  # legacy single-category node
                    cats = [int(tree.threshold[i])]
                bins = [m.cat_to_bin[c] for c in cats if c in m.cat_to_bin]
                for b in bins:
                    member_bins[i, b] = True
                t.threshold_bin[i] = bins[0] if bins else 0
            else:
                t.threshold_bin[i] = int(
                    m.value_to_bin(np.array([tree.threshold[i]]))[0])
            if m.missing_type == MissingType.NAN:
                t.nan_bin[i] = m.num_bin - 1
        t.cat_member_bins = member_bins
        return t

    def init_from_model(self, other: "GBDT") -> None:
        """Prime this booster with an existing model's trees and keep
        boosting (continued training)."""
        k = self.num_tree_per_iteration
        ok = getattr(other, "num_tree_per_iteration", 1)
        if ok != k:
            raise ValueError(f"init_model has {ok} trees/iteration, this "
                             f"training configuration needs {k}")
        self._pending = []
        self._models_list = [self._align_loaded_tree(t) for t in other.models]
        self.iter_ = len(self._models_list) // max(k, 1)
        # the loaded first tree already carries any boost-from-average bias
        self._pending_bias[:] = 0.0
        self._rebuild_scores()

    def merge_from(self, other: "GBDT") -> None:
        """Append another booster's trees to this model
        (reference c_api.h:489 LGBM_BoosterMerge; GBDT::MergeFrom).
        Thresholds re-bin against THIS dataset's mappers so the appended
        trees join the binned score/walk paths."""
        k = self.num_tree_per_iteration
        ok = getattr(other, "num_tree_per_iteration", 1)
        if ok != k:
            raise ValueError(f"cannot merge: {ok} trees/iteration vs {k}")
        merged = self.models + [self._align_loaded_tree(t)
                                for t in other.models]
        self.models = merged
        self.iter_ = len(self._models_list) // max(k, 1)
        self._rebuild_scores()

    def shuffle_models(self, start_iter: int = 0,
                       end_iter: int = -1) -> None:
        """Shuffle tree-iteration order in [start_iter, end_iter)
        (reference c_api.h:497 LGBM_BoosterShuffleModels;
        GBDT::ShuffleModels) — used by the refit flow to decorrelate."""
        k = max(self.num_tree_per_iteration, 1)
        models = self.models
        n_iter = len(models) // k
        s = max(0, int(start_iter))
        e = n_iter if end_iter <= 0 else min(int(end_iter), n_iter)
        if e - s <= 1:
            return
        order = np.arange(n_iter)
        rng = np.random.RandomState(int(self.config.seed) + 1)
        mid = order[s:e].copy()
        rng.shuffle(mid)
        order[s:e] = mid
        self.models = [models[i * k + j] for i in order for j in range(k)]
        self._rebuild_scores()

    def reset_train_data(self, new_train: Dataset) -> None:
        """Swap the training dataset under the existing model (reference
        GBDT::ResetTrainingData; c_api.h:478).  The new dataset aligns to
        this model's bin mappers (construct-with-reference), every
        data-dependent piece rebuilds through the normal setup path, the
        trees re-align, and scores rebuild — continued training then
        proceeds on the new rows."""
        if not new_train.constructed and new_train.reference is None \
                and self.train_set is not None:
            new_train.reference = self.train_set
        self._flush_trees()
        models = self._models_list
        valid_state = (self.valid_sets, self.valid_scores,
                       self.valid_metrics)
        self._init_train(new_train)   # construct + upload + learner +
        #                               objective/metric re-init + score0
        self.valid_sets, self.valid_scores, self.valid_metrics = valid_state
        if models:
            k = max(self.num_tree_per_iteration, 1)
            self._pending = []
            self._models_list = [self._align_loaded_tree(t) for t in models]
            self.iter_ = len(self._models_list) // k
            # the loaded first tree already carries any boost-from-average
            # bias (same contract as init_from_model)
            self._pending_bias[:] = 0.0
            self._rebuild_scores()

    def refit_trees(self, source: "GBDT", leaf_preds: np.ndarray) -> None:
        """Re-learn every loaded tree's leaf values on THIS dataset with the
        tree structures fixed (reference gbdt.cpp:285 RefitTree +
        serial_tree_learner.cpp:211 FitByExistingTree): scores restart from
        the init score, gradients are recomputed per iteration, each leaf's
        new value is the closed-form output of its (fixed) row set, mixed as
        decay*old + (1-decay)*new."""
        if self.objective is None:
            raise ValueError("cannot refit without an objective")
        k = self.num_tree_per_iteration
        any_linear = any(t.is_linear for t in source.models)
        if any_linear and getattr(self, "X_raw_dev", None) is None:
            # linear leaves predict from raw values; refit needs them on
            # device even if this booster trains plain trees
            if self.train_set.raw_used is None:
                raise ValueError(
                    "refit of a linear-tree model needs raw feature "
                    "values; construct the dataset with linear_tree=true")
            self.X_raw_dev = jnp.asarray(self.train_set.raw_used)
        trees = [self._align_loaded_tree(t) for t in source.models]
        n = self.num_data
        if leaf_preds.shape != (n, len(trees)):
            raise ValueError(f"leaf_preds shape {leaf_preds.shape} != "
                             f"({n}, {len(trees)})")
        decay = float(self.config.refit_decay_rate)
        sp = self.learner.split_params
        md = self.train_set.metadata
        shape = (n,) if k == 1 else (n, k)
        score = np.zeros(shape, np.float32)
        if md.init_score is not None:
            score = score + md.init_score.reshape(shape).astype(np.float32)
        for it in range(len(trees) // max(k, 1)):
            grad, hess = self.objective.get_gradients(jnp.asarray(score))
            grad = np.asarray(grad)
            hess = np.asarray(hess)
            for cid in range(k):
                ti = it * k + cid
                tree = trees[ti]
                g = grad if k == 1 else grad[:, cid]
                h = hess if k == 1 else hess[:, cid]
                lp = leaf_preds[:, ti]
                nl = tree.num_leaves
                sum_g = np.bincount(lp, weights=g, minlength=nl)[:nl]
                sum_h = np.bincount(lp, weights=h, minlength=nl)[:nl] + EPSILON
                new_out = np.asarray(_leaf_output_fn(
                    jnp.asarray(sum_g, jnp.float32),
                    jnp.asarray(sum_h, jnp.float32), sp), np.float64)
                new_out *= tree.shrinkage
                old_vals = tree.leaf_value[:len(new_out)].copy()
                tree.leaf_value = (decay * old_vals +
                                   (1.0 - decay) * new_out)
                tree.leaf_count = np.bincount(lp, minlength=nl)[:nl].astype(
                    np.int64)
                if tree.is_linear:
                    # linear leaves keep their fitted coefficients (the
                    # reference's FitByExistingTree copies the tree and
                    # refits only the leaf OUTPUT); shifting the constant
                    # by the output delta re-centers the linear model on
                    # the new rows consistently with the refit value
                    shift = tree.leaf_value - old_vals
                    tree.leaf_const = tree.leaf_const[:len(shift)] + shift
                    from ..learner.linear import linear_score_delta
                    lf, fm, co, lconst, lval = \
                        self._linear_device_arrays(tree)
                    delta = np.asarray(linear_score_delta(
                        self.X_raw_dev, jnp.asarray(lp, jnp.int32), lf, fm,
                        co, lconst, lval, 1.0), np.float32)
                else:
                    delta = tree.leaf_value[lp].astype(np.float32)
                if k == 1:
                    score += delta
                else:
                    score[:, cid] += delta
        self._pending = []
        self._models_list = trees
        self.iter_ = len(trees) // max(k, 1)
        self._pending_bias[:] = 0.0
        self.score = jnp.asarray(score)

    # -- checkpoint/resume (resilience/checkpoint.py rides these) ------------
    def capture_checkpoint_arrays(self) -> Dict[str, Any]:
        """The mutable boosting state beyond the model text, pulled to
        host with EXACT bits: the f32 train/valid scores (rebuilding
        them from trees re-rounds in a different order and can drift
        the last ulp, forking the remaining trajectory), the CEGB
        used-feature set, and the lagged stump-stop bookkeeping."""
        prev = getattr(self, "_prev_iter_leaves", None)
        return {
            "score": np.asarray(self.score),
            "valid_names": [name for name, _ in self.valid_sets],
            "valid_scores": [np.asarray(s) for s in self.valid_scores],
            "cegb_used": (None if self._cegb_coupled is None
                          else np.asarray(self._cegb_used)),
            "prev_iter_leaves": (None if prev is None else
                                 [int(x) for x in jax.device_get(prev)]),
        }

    def restore_boosting_state(self, model_text: str, iteration: int,
                               score: np.ndarray,
                               valid_scores: List[np.ndarray],
                               cegb_used: Optional[np.ndarray] = None,
                               prev_iter_leaves: Optional[List[int]] = None
                               ) -> None:
        """Continue boosting from a checkpoint: trees reload from model
        text (%.17g round-trips every double) and re-key onto this
        dataset's binning; scores restore from the saved f32 bits
        instead of a tree-walk rebuild.  With the same data, params and
        seeds the continuation is bit-identical to a run that never
        stopped."""
        if self.name in ("dart", "rf"):
            raise ValueError(
                f"checkpoint/resume is not supported for boosting="
                f"{self.name}: its per-tree weight/averaging caches "
                f"(DART drop weights, RF running tree sums) are not part "
                f"of the model text")
        from .model_text import string_to_model
        loaded = string_to_model(model_text, self.config)
        k = self.num_tree_per_iteration
        ok = getattr(loaded, "num_tree_per_iteration", 1)
        if ok != k:
            raise ValueError(f"checkpoint model has {ok} trees/iteration, "
                             f"this training configuration needs {k}")
        self._pending = []
        self._models_list = [self._align_loaded_tree(t)
                             for t in loaded.models]
        self.iter_ = int(iteration)
        # tree 0 already carries any boost-from-average bias
        self._pending_bias[:] = 0.0
        score = np.asarray(score, np.float32)
        want = (self.num_data,) if k == 1 else (self.num_data, k)
        if score.shape != want:
            raise ValueError(f"checkpoint score shape {score.shape} does "
                             f"not match this dataset ({want})")
        self.score = jnp.asarray(score)
        if len(valid_scores) != len(self.valid_scores):
            raise ValueError(
                f"checkpoint carries {len(valid_scores)} validation score "
                f"sets, this run registered {len(self.valid_scores)} "
                f"valid sets")
        self.valid_scores = [jnp.asarray(np.asarray(vs, np.float32))
                             for vs in valid_scores]
        if cegb_used is not None and self._cegb_coupled is not None:
            self._cegb_used[:] = np.asarray(cegb_used, bool)
        self._prev_iter_leaves = (None if prev_iter_leaves is None else
                                  [int(x) for x in prev_iter_leaves])

    # -- model management ----------------------------------------------------
    def rollback_one_iter(self) -> None:
        """Reference gbdt.cpp:454 RollbackOneIter."""
        if self.iter_ <= 0:
            return
        k = self.num_tree_per_iteration
        for _ in range(k):
            if self.models:
                self.models.pop()
        self.iter_ -= 1
        # scores must be rebuilt from remaining trees
        self._rebuild_scores()

    def _rebuild_scores(self) -> None:
        k = self.num_tree_per_iteration
        n = self.num_data
        shape = (n,) if k == 1 else (n, k)
        score0 = np.zeros(shape, np.float32)
        md = self.train_set.metadata
        if md.init_score is not None:
            score0 += md.init_score.reshape(shape).astype(np.float32)
        elif not self.models and self.config.boost_from_average and \
                self.objective is not None:
            # with no trees left the bias is no longer carried by tree 0;
            # restore it so gradients and the next first tree stay consistent
            score0 += (np.float32(self._pending_bias[0]) if k == 1 else
                       self._pending_bias[None, :].astype(np.float32))
        self.score = jnp.asarray(score0)
        if self.models:
            score = self.score
            for t, tree in enumerate(self.models):
                cid = t % k
                if tree.is_linear:
                    from ..learner.linear import linear_score_delta
                    idx_f = self._walk(self.X_dev, jnp.asarray(tree.split_feature),
                               jnp.asarray(tree.threshold_bin),
                               jnp.asarray(tree.nan_bin),
                               _tree_cat_member(tree),
                               jnp.asarray(tree.decision_type.astype(np.int32)),
                               jnp.asarray(tree.left_child),
                               jnp.asarray(tree.right_child),
                               jnp.arange(tree.max_leaves, dtype=jnp.float32),
                               jnp.asarray(tree.num_leaves, dtype=jnp.int32))
                    lf, fm, co, lconst, lval = self._linear_device_arrays(tree)
                    delta = linear_score_delta(
                        self.X_raw_dev, idx_f.astype(jnp.int32), lf, fm, co,
                        lconst, lval, 1.0)
                else:
                    delta = self._walk(
                        self.X_dev, jnp.asarray(tree.split_feature),
                               jnp.asarray(tree.threshold_bin),
                               jnp.asarray(tree.nan_bin),
                               _tree_cat_member(tree),
                               jnp.asarray(tree.decision_type.astype(np.int32)),
                               jnp.asarray(tree.left_child),
                               jnp.asarray(tree.right_child),
                               jnp.asarray(tree.leaf_value, dtype=jnp.float32),
                               jnp.asarray(tree.num_leaves, dtype=jnp.int32))
                if k == 1:
                    score = score + delta
                else:
                    score = score.at[:, cid].add(delta)
            self.score = score

    @property
    def current_iteration(self) -> int:
        return self.iter_

    def num_trees(self) -> int:
        return len(self.models)

    # model text IO lives in model_text.py
    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1,
                             importance_type: int = 0) -> str:
        from .model_text import model_to_string
        return model_to_string(self, start_iteration, num_iteration)

    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """Reference Booster::FeatureImportance (gbdt.cpp).

        Returns a full-length array over the ORIGINAL columns (the reference
        reports num_total_features entries; trivially-filtered columns get
        zero), so ``zip(X.columns, importances)`` works."""
        imp = np.zeros(self.num_features, np.float64)
        for tree in self.models:
            for i in range(tree.num_leaves - 1):
                f = tree.split_feature[i]
                if f >= 0:
                    if importance_type == "split":
                        imp[f] += 1.0
                    else:
                        imp[f] += max(tree.split_gain[i], 0.0)
        real_map, num_total, _ = self.feature_mapping()
        full = np.zeros(num_total, np.float64)
        full[real_map] = imp
        return full

    def feature_mapping(self):
        """(inner->original index map, num original columns, original names) —
        the single source for mapping tree-internal feature indices back to
        the user's columns (trained models: Dataset's trivial-filter map;
        loaded models: identity over max_feature_idx+1)."""
        ts = self.train_set
        if ts is not None and ts.used_feature_map is not None:
            return (np.asarray(ts.used_feature_map),
                    int(ts.num_total_features), list(ts.feature_names_))
        num_total = int(getattr(self, "loaded_num_total", self.num_features))
        real_map = np.asarray(getattr(self, "loaded_real_map",
                                      np.arange(self.num_features)))
        names = getattr(self, "loaded_feature_names", None) or \
            [f"Column_{i}" for i in range(num_total)]
        return real_map, num_total, names
