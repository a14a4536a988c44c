"""Flat-array decision tree model + jitted prediction.

TPU-native re-implementation of the reference tree model
(reference: include/LightGBM/tree.h:25 ``Tree`` — flat arrays
``split_feature_``, ``threshold_``, ``left_child_``, ``right_child_``,
``leaf_value_``; child pointers use ``~leaf_index`` for leaves, and
prediction is a branchy walk, tree.h:133 ``Tree::Predict``).

Here every tree of a model shares the same max size (num_leaves from config),
so a whole boosted ensemble stacks into (T, ...) arrays and prediction is one
jitted vectorized tree walk over (rows x trees) — no per-node branching, the
walk advances all rows one level per iteration of a ``lax.while_loop``.

decision_type bit layout follows the reference (tree.h decision_type):
  bit0: categorical, bit1: default_left, bits 2-3: missing type
  (0 none, 1 zero, 2 nan).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Tree", "TreeBatch", "predict_binned", "predict_raw",
           "SHAPE_BUCKETS", "bucket_rows", "pad_rows",
           "ensemble_serve_fields", "predict_raw_ensemble"]

CAT_MASK = 1
DEFAULT_LEFT_MASK = 2
MISSING_ZERO = 1 << 2
MISSING_NAN = 2 << 2

# Row-count ladder for compiled prediction: requests pad up to the next
# bucket so arbitrary batch sizes hit a handful of compiled programs
# instead of one XLA trace per novel shape.  Beyond the ladder, sizes
# round up to the next MULTIPLE of the top bucket — waste stays under
# one bucket (vs up to 2x for power-of-two rounding) while the distinct
# compiled-shape count stays bounded.
SHAPE_BUCKETS = (1, 8, 64, 512, 4096)


def bucket_rows(n: int, ladder=SHAPE_BUCKETS) -> int:
    """Smallest ladder bucket holding ``n`` rows (multiple of the top
    bucket above the ladder's end)."""
    if n <= 0:
        return ladder[0]
    for b in ladder:
        if n <= b:
            return b
    top = ladder[-1]
    return (n + top - 1) // top * top


def pad_rows(X: np.ndarray, ladder=SHAPE_BUCKETS) -> np.ndarray:
    """Zero-pad ``X`` (N, F) up to its row bucket.  Padding rows cannot
    perturb real rows: every prediction path reduces per row."""
    nb = bucket_rows(X.shape[0], ladder)
    if nb == X.shape[0]:
        return X
    return np.concatenate(
        [X, np.zeros((nb - X.shape[0], X.shape[1]), X.dtype)], axis=0)


@dataclasses.dataclass
class Tree:
    """Host-side view of one trained tree (numpy arrays).

    Internal node arrays have length num_leaves-1 (only the first
    ``num_leaves_actual - 1`` entries are meaningful); leaf arrays have length
    num_leaves.  Child pointers >= 0 index internal nodes; negative pointers
    are leaves encoded as ``~leaf_index`` (reference tree.h convention).
    """

    num_leaves: int                    # actual leaves
    split_feature: np.ndarray          # (L-1,) int32, inner feature index
    threshold_bin: np.ndarray          # (L-1,) int32
    nan_bin: np.ndarray                # (L-1,) int32 bin holding NaN (-1: none)
    threshold: np.ndarray              # (L-1,) float64 raw-value threshold
    decision_type: np.ndarray          # (L-1,) uint8
    left_child: np.ndarray             # (L-1,) int32
    right_child: np.ndarray            # (L-1,) int32
    split_gain: np.ndarray             # (L-1,) float32
    internal_value: np.ndarray         # (L-1,) float64
    internal_weight: np.ndarray        # (L-1,) float64
    internal_count: np.ndarray         # (L-1,) int64
    leaf_value: np.ndarray             # (L,) float64
    leaf_weight: np.ndarray            # (L,) float64
    leaf_count: np.ndarray             # (L,) int64
    shrinkage: float = 1.0
    # Categorical set splits (reference tree.h:85 SplitCategorical):
    # cat nodes store threshold = RANK into cat_boundaries; the flat
    # cat_threshold uint32 words are a bitset over RAW category values
    # (cat_boundaries[rank]..cat_boundaries[rank+1] words per node).
    cat_boundaries: Optional[np.ndarray] = None   # (num_cat+1,) int32
    cat_threshold: Optional[np.ndarray] = None    # flat uint32 words
    # runtime-only binned membership for training-time walks (not
    # serialized; rebuilt from the bin mappers on load): (L-1, B) bool
    cat_member_bins: Optional[np.ndarray] = None
    # Linear-tree fields (reference tree.h is_linear_/leaf_const_/
    # leaf_coeff_/leaf_features_): per-leaf linear models on branch
    # features; leaf_features holds REAL column indices; prediction is
    # leaf_const + sum(coef * x), falling back to leaf_value when any
    # leaf feature is NaN.
    is_linear: bool = False
    leaf_const: Optional[np.ndarray] = None       # (L,) float64
    leaf_coeff: Optional[List[List[float]]] = None
    leaf_features: Optional[List[List[int]]] = None        # REAL indices
    leaf_features_inner: Optional[List[List[int]]] = None  # inner indices

    @property
    def max_leaves(self) -> int:
        return len(self.leaf_value)

    def num_cat_nodes(self) -> int:
        return 0 if self.cat_boundaries is None else \
            len(self.cat_boundaries) - 1

    def cat_values(self, node: int) -> List[int]:
        """Raw category values in the node's LEFT set."""
        if self.cat_boundaries is None:
            return [int(self.threshold[node])]
        rank = int(self.threshold[node])
        lo = int(self.cat_boundaries[rank])
        hi = int(self.cat_boundaries[rank + 1])
        return [w * 32 + b for w in range(hi - lo) for b in range(32)
                if int(self.cat_threshold[lo + w]) & (1 << b)]

    def cat_decision(self, node: int, value: float) -> bool:
        """Set-membership decision for a categorical node on a RAW value
        (reference tree.h FindInBitset + Tree::CategoricalDecision).
        True -> go left."""
        if np.isnan(value):
            return bool(self.decision_type[node] & DEFAULT_LEFT_MASK)
        iv = int(value)
        if iv < 0 or iv != value:
            return False
        if self.cat_boundaries is None:
            return iv == int(self.threshold[node])  # legacy single-category
        rank = int(self.threshold[node])
        lo = int(self.cat_boundaries[rank])
        hi = int(self.cat_boundaries[rank + 1])
        word = iv // 32
        if word >= hi - lo:
            return False
        return bool((int(self.cat_threshold[lo + word]) >> (iv % 32)) & 1)

    def num_internal(self) -> int:
        return max(self.num_leaves - 1, 0)

    def shrink(self, rate: float) -> None:
        """In-place shrinkage (reference tree.h Shrinkage)."""
        self.leaf_value = self.leaf_value * rate
        self.internal_value = self.internal_value * rate
        if self.is_linear:
            self.leaf_const = self.leaf_const * rate
            self.leaf_coeff = [[c * rate for c in cs]
                               for cs in self.leaf_coeff]
        self.shrinkage *= rate

    def add_bias(self, val: float) -> None:
        self.leaf_value = self.leaf_value + val
        self.internal_value = self.internal_value + val
        if self.is_linear:
            self.leaf_const = self.leaf_const + val

    def linear_predict_row(self, leaf: int, row: np.ndarray) -> float:
        """Host reference linear-leaf evaluation (tree.cpp
        PredictionFunLinear): NaN in any leaf feature -> plain output."""
        feats = (self.leaf_features_inner if self.leaf_features_inner
                 is not None else self.leaf_features)[leaf]
        total = float(self.leaf_const[leaf])
        for f, c in zip(feats, self.leaf_coeff[leaf]):
            v = row[f]
            if np.isnan(v):
                return float(self.leaf_value[leaf])
            total += c * v
        return total

    def predict(self, X: np.ndarray) -> np.ndarray:
        """Raw-feature prediction, host reference implementation
        (tree.h:133 Tree::Predict).  Used for testing; batch prediction goes
        through TreeBatch."""
        out = np.empty(len(X), dtype=np.float64)
        for i, row in enumerate(X):
            node = 0
            if self.num_leaves <= 1:
                out[i] = self.leaf_value[0]
                continue
            while node >= 0:
                f = self.split_feature[node]
                v = row[f]
                dt = self.decision_type[node]
                if dt & CAT_MASK:
                    left = self.cat_decision(node, v)
                else:
                    if np.isnan(v):
                        if (dt >> 2) == 2:  # missing nan
                            left = bool(dt & DEFAULT_LEFT_MASK)
                        else:
                            v = 0.0
                            left = v <= self.threshold[node]
                    else:
                        left = v <= self.threshold[node]
                node = self.left_child[node] if left else self.right_child[node]
            out[i] = (self.linear_predict_row(~node, row) if self.is_linear
                      else self.leaf_value[~node])
        return out


def _floor_f32(a: np.ndarray) -> np.ndarray:
    """Largest float32 <= ``a``.  The device predictors compare float32
    feature values with float32 thresholds; with the threshold rounded
    DOWN, ``v <= threshold`` decides as the float64 compare of the model
    text does for every float32 ``v``.  Rounded to nearest, a value equal
    to the float32 just above a threshold went to the left child."""
    a = np.asarray(a, np.float64)
    with np.errstate(over="ignore"):
        f = a.astype(np.float32)
    return np.where(f.astype(np.float64) > a,
                    np.nextafter(f, np.float32(-np.inf)), f)


class TreeBatch:
    """Stacked device arrays for T trees of identical max size; the ensemble
    prediction structure (replaces the reference's per-tree virtual calls in
    gbdt_prediction.cpp with one vectorized walk)."""

    FIELDS = ("split_feature", "threshold_bin", "threshold", "decision_type",
              "left_child", "right_child", "leaf_value")

    def __init__(self, trees: List[Tree]):
        if not trees:
            raise ValueError("no trees")
        self.num_trees = len(trees)
        self.max_leaves = max(max(t.max_leaves, t.num_leaves) for t in trees)
        ml = self.max_leaves

        def stack(attr, size, dtype=None, fill=0, cast=None):
            arrs = []
            for t in trees:
                a = np.asarray(getattr(t, attr))
                if len(a) < size:
                    a = np.concatenate([a, np.full(size - len(a), fill,
                                                   a.dtype if a.size else
                                                   np.float64)])
                arrs.append(a[:size])
            out = np.stack(arrs)
            if cast is not None:
                out = cast(out)
            return jnp.asarray(out if dtype is None else out.astype(dtype))

        self.split_feature = stack("split_feature", ml - 1, np.int32)
        self.threshold_bin = stack("threshold_bin", ml - 1, np.int32)
        self.nan_bin = stack("nan_bin", ml - 1, np.int32, fill=-1)
        self.threshold = stack("threshold", ml - 1, cast=_floor_f32)
        self.decision_type = stack("decision_type", ml - 1, np.uint8)
        self.left_child = stack("left_child", ml - 1, np.int32)
        self.right_child = stack("right_child", ml - 1, np.int32)
        self.leaf_value = stack("leaf_value", ml, np.float32)
        self.num_leaves = jnp.asarray(np.array([t.num_leaves for t in trees],
                                               dtype=np.int32))

        # categorical-set arrays: binned membership (training walks) and
        # raw-value bitset words (inference walks); width 1 when no tree
        # has categorical nodes so the jitted walks stay uniform
        bm = max([1] + [t.cat_member_bins.shape[1] for t in trees
                        if t.cat_member_bins is not None])
        member = np.zeros((len(trees), ml - 1, bm), bool)
        for ti, t in enumerate(trees):
            if t.cat_member_bins is not None:
                m = t.cat_member_bins
                member[ti, :m.shape[0], :m.shape[1]] = m
        self.cat_member = jnp.asarray(member)

        wmax = 1
        for t in trees:
            if t.cat_boundaries is not None:
                for r in range(len(t.cat_boundaries) - 1):
                    wmax = max(wmax, int(t.cat_boundaries[r + 1]) -
                               int(t.cat_boundaries[r]))
            else:  # legacy single-category nodes: threshold IS the category
                for i in range(t.num_leaves - 1):
                    if t.decision_type[i] & CAT_MASK:
                        wmax = max(wmax, int(t.threshold[i]) // 32 + 1)
        words = np.zeros((len(trees), ml - 1, wmax), np.uint32)
        for ti, t in enumerate(trees):
            for i in range(t.num_leaves - 1):
                if not (t.decision_type[i] & CAT_MASK):
                    continue
                if t.cat_boundaries is not None:
                    rank = int(t.threshold[i])
                    lo = int(t.cat_boundaries[rank])
                    hi = int(t.cat_boundaries[rank + 1])
                    words[ti, i, :hi - lo] = t.cat_threshold[lo:hi]
                else:
                    v = int(t.threshold[i])
                    words[ti, i, v // 32] |= np.uint32(1 << (v % 32))
        self.cat_words = jnp.asarray(words)

        # linear-tree leaf models (tree.h leaf_coeff_/leaf_const_)
        self.has_linear = any(t.is_linear for t in trees)
        lk = 1
        if self.has_linear:
            for t in trees:
                if t.is_linear:
                    lk = max(lk, max((len(f) for f in
                                      (t.leaf_features_inner or
                                       t.leaf_features)), default=1))
        lconst = np.zeros((len(trees), ml), np.float32)
        lcoef = np.zeros((len(trees), ml, lk), np.float32)
        lfeat = np.zeros((len(trees), ml, lk), np.int32)
        lfmask = np.zeros((len(trees), ml, lk), np.float32)
        lflag = np.zeros((len(trees),), np.float32)
        for ti, t in enumerate(trees):
            if not t.is_linear:
                continue
            lflag[ti] = 1.0
            lconst[ti, :len(t.leaf_const)] = t.leaf_const
            feats = t.leaf_features_inner if t.leaf_features_inner \
                is not None else t.leaf_features
            for leaf, (fs, cs) in enumerate(zip(feats, t.leaf_coeff)):
                lfeat[ti, leaf, :len(fs)] = fs
                lfmask[ti, leaf, :len(fs)] = 1.0
                lcoef[ti, leaf, :len(cs)] = cs
        self.leaf_const = jnp.asarray(lconst)
        self.leaf_coef = jnp.asarray(lcoef)
        self.leaf_feat = jnp.asarray(lfeat)
        self.leaf_fmask = jnp.asarray(lfmask)
        self.linear_flag = jnp.asarray(lflag)

        # Dense-walk path matrices (the MXU inference formulation,
        # _walk_raw_dense): path_dir[n, l] = +1 when node n sits on leaf
        # l's root path expecting a LEFT decision, -1 expecting RIGHT;
        # a row's leaf is the unique l whose satisfied-condition count
        # S = dec @ path_dir + plen_right equals the path length.  Leaf
        # slots beyond num_leaves get an unreachable path length.
        self.has_cat = any(bool(np.bitwise_and(
            np.asarray(t.decision_type[:max(t.num_leaves - 1, 0)],
                       np.uint8), CAT_MASK).any()) for t in trees)
        pd = np.zeros((len(trees), max(ml - 1, 1), ml), np.int8)
        pr = np.zeros((len(trees), ml), np.float32)
        pt = np.full((len(trees), ml), 1e9, np.float32)
        for ti, t in enumerate(trees):
            if t.num_leaves <= 1:
                pt[ti, 0] = 0.0
                continue
            lc = np.asarray(t.left_child)
            rc = np.asarray(t.right_child)
            work = [(0, [])]
            while work:
                node, path = work.pop()
                for child, d in ((int(lc[node]), 1), (int(rc[node]), -1)):
                    p2 = path + [(node, d)]
                    if child < 0:
                        leaf = ~child
                        if leaf < ml:
                            for nn_, dd in p2:
                                pd[ti, nn_, leaf] = dd
                            pr[ti, leaf] = float(
                                sum(1 for _, dd in p2 if dd < 0))
                            pt[ti, leaf] = float(len(p2))
                    else:
                        work.append((child, p2))
        self.path_dir = jnp.asarray(pd)
        self.plen_right = jnp.asarray(pr)
        self.plen_total = jnp.asarray(pt)

    def as_tuple(self):
        return (self.split_feature, self.threshold_bin, self.nan_bin,
                self.cat_member, self.decision_type, self.left_child,
                self.right_child, self.leaf_value, self.num_leaves)


@functools.partial(jax.jit, static_argnames=("freq", "mode"))
def predict_raw_early_stop(fields, X, margin, stopped0, *, freq: int,
                           mode: str):
    """Raw prediction with per-row margin-based early exit across trees
    (reference src/boosting/prediction_early_stop.cpp:54 binary — stop when
    2|raw| > margin — and :25 multiclass — stop when top-2 margin exceeds
    the threshold; checked every ``freq`` trees).  Stopped rows freeze
    their partial sum (the reference returns the truncated score); the
    tree loop exits entirely once every row has stopped.

    fields: per-class tuple trees-first arrays as in predict_raw; for
    multiclass a list of per-class field tuples sharing the walk.
    stopped0: (N,) bool initial stop mask — shape-bucket padding rows
    ride in pre-stopped so they can never hold the tree loop open past
    the point where every real row has exited.
    """
    per_class = fields
    k = len(per_class)
    t_total = per_class[0][0].shape[0]
    n = X.shape[0]

    def tree_at(c, t):
        return tuple(a[t] for a in per_class[c])

    def body(state):
        t, out, stopped = state
        deltas = []
        for c in range(k):
            val, _ = _walk_raw(X, *tree_at(c, t))
            deltas.append(jnp.where(stopped, 0.0, val))
        out = out + jnp.stack(deltas, axis=1)
        check = ((t + 1) % freq == 0)
        if mode == "binary":
            stop_now = 2.0 * jnp.abs(out[:, 0]) > margin
        else:
            top2 = jax.lax.top_k(out, 2)[0]
            stop_now = (top2[:, 0] - top2[:, 1]) > margin
        stopped = stopped | (check & stop_now)
        return t + 1, out, stopped

    def cond(state):
        t, _, stopped = state
        return (t < t_total) & jnp.logical_not(jnp.all(stopped))

    _, out, _ = jax.lax.while_loop(
        cond, body, (jnp.asarray(0, jnp.int32), jnp.zeros((n, k), jnp.float32),
                     stopped0))
    return out


def _walk_impl(fetch_bin, n, split_feature, threshold_bin, nan_bin,
               cat_member, decision_type, left_child, right_child,
               leaf_value, num_leaves):
    """Shared body of the binned tree walkers: ``fetch_bin(nd, f)`` returns
    each row's FEATURE-space bin code for node feature ``f`` — plain
    column take for feature-space matrices, bundle-column decode under
    EFB.  One implementation so walk semantics (NaN routing, categorical
    membership, default-left) can never diverge between the two."""
    node = jnp.where(num_leaves <= 1, -1, 0) * jnp.ones((n,), jnp.int32)
    bm = cat_member.shape[1]

    def cond(state):
        node, _ = state
        return jnp.any(node >= 0)

    def body(state):
        node, out = state
        active = node >= 0
        nd = jnp.maximum(node, 0)
        f = split_feature[nd]
        thr = threshold_bin[nd]
        dt = decision_type[nd]
        b = fetch_bin(nd, f)
        is_cat = (dt & CAT_MASK) != 0
        dleft = (dt & DEFAULT_LEFT_MASK) != 0
        # the NaN bin is the feature's last bin, above any real threshold,
        # so "missing right" is automatic; "missing left" overrides via
        # nan_bin
        is_nanbin = b == nan_bin[nd]
        cat_go = cat_member.reshape(-1)[nd * bm + jnp.minimum(b, bm - 1)]
        go_left = jnp.where(is_cat, cat_go,
                            jnp.where(is_nanbin, dleft, b <= thr))
        nxt = jnp.where(go_left, left_child[nd], right_child[nd])
        new_node = jnp.where(active, nxt, node)
        out = jnp.where(active & (new_node < 0),
                        leaf_value[jnp.maximum(~new_node, 0)], out)
        return new_node, out

    out0 = jnp.where(num_leaves <= 1,
                     jnp.broadcast_to(leaf_value[0], (n,)),
                     jnp.zeros((n,), jnp.float32))
    node, out = jax.lax.while_loop(cond, body, (node, out0))
    return out


def _device_path_matrices(left_child, right_child, num_leaves, L):
    """Path matrices built ON DEVICE with one pass over the node arrays
    (valid because the growers allocate child node ids after their
    parents).  Rebuilt per call — ~L tiny scatter steps, negligible next
    to the walk."""
    nn = left_child.shape[0]

    def build(i, carry):
        pathmat, leaf_dir, plen_r, plen_t = carry
        active = i < num_leaves - 1
        base = pathmat[i]
        for child, d in ((left_child[i], 1), (right_child[i], -1)):
            vec = base.at[i].set(jnp.int8(d))
            isleaf = child < 0
            nidx = jnp.where(active & jnp.logical_not(isleaf), child, nn)
            pathmat = pathmat.at[nidx].set(vec, mode="drop")
            lidx = jnp.where(active & isleaf, ~child, L)
            leaf_dir = leaf_dir.at[:, lidx].set(vec, mode="drop")
            plen_r = plen_r.at[lidx].set(
                jnp.sum((vec == -1).astype(jnp.float32)), mode="drop")
            plen_t = plen_t.at[lidx].set(
                jnp.sum((vec != 0).astype(jnp.float32)), mode="drop")
        return pathmat, leaf_dir, plen_r, plen_t

    pathmat0 = jnp.zeros((nn, nn), jnp.int8)
    leaf_dir0 = jnp.zeros((nn, L), jnp.int8)
    plen_r0 = jnp.zeros((L,), jnp.float32)
    plen_t0 = jnp.full((L,), 1e9, jnp.float32)
    _, leaf_dir, plen_r, plen_t = jax.lax.fori_loop(
        0, nn, build, (pathmat0, leaf_dir0, plen_r0, plen_t0))
    return leaf_dir, plen_r, plen_t


@jax.jit
def _walk_binned_dense(bins, split_feature, threshold_bin, nan_bin,
                       decision_type, left_child, right_child, leaf_value,
                       num_leaves):
    """Dense matmul walk on BINNED data for one (categorical-free,
    non-EFB) tree whose arrays live on device (the deferred grown trees
    driving valid-set score updates).  The path matrices are built
    on-device with a single pass over the nodes — valid because the
    growers allocate child node ids AFTER their parents — then the leaf
    resolution is the same satisfied-condition count as
    :func:`_walk_raw_dense`.  Replaces a depth-deep gather walk."""
    P = _onehot_feature_lookup(bins.astype(jnp.float32), split_feature)
    return _binned_dense_from_codes(P, threshold_bin, nan_bin,
                                    decision_type, left_child,
                                    right_child, leaf_value, num_leaves)


def _binned_dense_from_codes(P, threshold_bin, nan_bin, decision_type,
                             left_child, right_child, leaf_value,
                             num_leaves):
    """Shared tail of the dense binned walks: decision + path-count leaf
    resolution from per-node FEATURE-space bin codes ``P`` (N, Nn)."""
    n = P.shape[0]
    L = leaf_value.shape[0]
    leaf_dir, plen_r, plen_t = _device_path_matrices(
        left_child, right_child, num_leaves, L)
    dleft = (decision_type & DEFAULT_LEFT_MASK) != 0
    dec = jnp.where(P == nan_bin[None, :].astype(jnp.float32),
                    dleft[None, :],
                    P <= threshold_bin[None, :]).astype(jnp.bfloat16)
    out, _ = _dense_leaf_out(dec, leaf_dir, plen_r, plen_t, leaf_value,
                             want_leaf=False)
    return jnp.where(num_leaves <= 1,
                     jnp.broadcast_to(leaf_value[0], (n,)), out)


@jax.jit
def _walk_binned_dense_efb(bins, efb_walk, split_feature, threshold_bin,
                           nan_bin, decision_type, left_child, right_child,
                           leaf_value, num_leaves):
    """Dense binned walk over an EFB-BUNDLED matrix: each node's bundle
    column rides the one-hot lookup, then the SAME decode closure the
    growers use (efb.make_bundle_decode, broadcast over (N, Nn)) maps
    bundle codes to feature space — no per-row gathers."""
    from ..efb import make_bundle_decode
    _, f_bundle, *_rest = efb_walk
    Pb = _onehot_feature_lookup(bins.astype(jnp.float32),
                                f_bundle[split_feature])
    Pf = make_bundle_decode(efb_walk)(
        Pb.astype(jnp.int32), split_feature[None, :]).astype(jnp.float32)
    return _binned_dense_from_codes(Pf, threshold_bin, nan_bin,
                                    decision_type, left_child,
                                    right_child, leaf_value, num_leaves)


@jax.jit
def _walk_binned(bins, split_feature, threshold_bin, nan_bin, cat_member,
                 decision_type, left_child, right_child, leaf_value,
                 num_leaves):
    """Vectorized tree walk on BINNED data for one tree.

    bins: (N, F) int; tree arrays as in TreeBatch rows; cat_member is the
    (L-1, B) categorical LEFT-set membership over bins.
    Returns (N,) float32 leaf values.
    """
    def fetch_bin(nd, f):
        return jnp.take_along_axis(bins, f[:, None],
                                   axis=1)[:, 0].astype(jnp.int32)

    return _walk_impl(fetch_bin, bins.shape[0], split_feature,
                      threshold_bin, nan_bin, cat_member, decision_type,
                      left_child, right_child, leaf_value, num_leaves)


@jax.jit
def _walk_binned_efb(bins, efb_walk, split_feature, threshold_bin, nan_bin,
                     cat_member, decision_type, left_child, right_child,
                     leaf_value, num_leaves):
    """_walk_binned over an EFB-bundled matrix: ``bins`` is (N, G)
    BUNDLE-space codes; each node's feature code is decoded from its
    bundle column (efb.make_bundle_decode — the same decode the growers
    use) before the threshold test.  ``efb_walk`` is the standard
    efb_arrays tuple (exp_map may be None; the decode ignores it)."""
    from ..efb import make_bundle_decode
    decode = make_bundle_decode(efb_walk)
    f_bundle = efb_walk[1]

    def fetch_bin(nd, f):
        v = jnp.take_along_axis(bins, f_bundle[f][:, None],
                                axis=1)[:, 0].astype(jnp.int32)
        return decode(v, f)

    return _walk_impl(fetch_bin, bins.shape[0], split_feature,
                      threshold_bin, nan_bin, cat_member, decision_type,
                      left_child, right_child, leaf_value, num_leaves)


def predict_binned(batch: TreeBatch, bins: jnp.ndarray,
                   num_iteration: Optional[int] = None) -> jnp.ndarray:
    """Sum of per-tree leaf outputs on binned rows (training-time scoring)."""
    fields = batch.as_tuple()
    t = batch.num_trees if num_iteration is None else min(num_iteration, batch.num_trees)

    def body(carry, tree_fields):
        return carry + _walk_binned(bins, *tree_fields), None

    sliced = tuple(a[:t] for a in fields)
    out, _ = jax.lax.scan(body, jnp.zeros((bins.shape[0],), jnp.float32), sliced)
    return out


@jax.jit
def _walk_raw(X, split_feature, threshold, cat_words, decision_type,
              left_child, right_child, leaf_value, num_leaves):
    """Vectorized walk on RAW float features for one tree (inference path).

    cat_words: (L-1, W) uint32 bitset over raw category values per node
    (reference tree.h FindInBitset)."""
    n = X.shape[0]
    node = jnp.where(num_leaves <= 1, -1, 0) * jnp.ones((n,), jnp.int32)
    w = cat_words.shape[1]

    def cond(state):
        return jnp.any(state[0] >= 0)

    def body(state):
        node, out, leaf = state
        active = node >= 0
        nd = jnp.maximum(node, 0)
        f = split_feature[nd]
        thr = threshold[nd]
        dt = decision_type[nd]
        v = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
        is_cat = (dt & CAT_MASK) != 0
        dleft = (dt & DEFAULT_LEFT_MASK) != 0
        miss_nan = (dt & (3 << 2)) == MISSING_NAN
        is_nan = jnp.isnan(v)
        v_num = jnp.where(is_nan & ~miss_nan, 0.0, v)
        go_left_num = jnp.where(is_nan & miss_nan, dleft, v_num <= thr)
        # categorical set membership on the raw value; NaN categoricals
        # follow default_left ("is bin 0 / the most frequent category in
        # the left set", recorded by the grower)
        vi = jnp.where(is_nan, -1.0, v).astype(jnp.int32)
        in_range = (vi >= 0) & (vi < w * 32) & \
            (vi.astype(jnp.float32) == jnp.where(is_nan, -1.0, v))
        word = cat_words.reshape(-1)[nd * w + jnp.clip(vi, 0, w * 32 - 1) // 32]
        bit = (word >> (jnp.clip(vi, 0) % 32).astype(jnp.uint32)) & 1
        go_left_cat = jnp.where(is_nan, dleft, in_range & (bit > 0))
        go_left = jnp.where(is_cat, go_left_cat, go_left_num)
        nxt = jnp.where(go_left, left_child[nd], right_child[nd])
        new_node = jnp.where(active, nxt, node)
        out = jnp.where(active & (new_node < 0),
                        leaf_value[jnp.maximum(~new_node, 0)], out)
        leaf = jnp.where(active & (new_node < 0),
                         jnp.maximum(~new_node, 0), leaf)
        return new_node, out, leaf

    out0 = jnp.where(num_leaves <= 1,
                     jnp.broadcast_to(leaf_value[0], (n,)),
                     jnp.zeros((n,), jnp.float32))
    leaf0 = jnp.zeros((n,), jnp.int32)
    node, out, leaf = jax.lax.while_loop(cond, body, (node, out0, leaf0))
    return out, leaf


def _onehot_feature_lookup(V, split_feature):
    """(N, Nn) per-node feature values via a one-hot contraction.
    Precision.HIGHEST: bf16-rounded values could flip near-threshold
    decisions (and uint16 bin codes exceed bf16's exact range)."""
    f_count = V.shape[1]
    onehot = (jnp.arange(f_count, dtype=jnp.int32)[:, None] ==
              split_feature[None, :]).astype(jnp.float32)
    return jax.lax.dot_general(V, onehot, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def _dense_leaf_out(dec, path_dir, plen_right, plen_total, leaf_value,
                    want_leaf=True):
    """Leaf resolution by satisfied-path-condition count.  0/1 decisions
    and +-1 directions are bf16-exact and the matmul accumulates in f32,
    so the equality test is exact."""
    S = jax.lax.dot_general(dec, path_dir.astype(jnp.bfloat16),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + \
        plen_right[None, :]
    hit = S == plen_total[None, :]
    out = jnp.sum(jnp.where(hit, leaf_value[None, :], 0.0), axis=1)
    if not want_leaf:
        return out, None
    return out, jnp.argmax(hit, axis=1).astype(jnp.int32)


def _walk_raw_dense(X, split_feature, threshold, decision_type, path_dir,
                    plen_right, plen_total, leaf_value, want_leaf=True):
    """Matmul-form tree walk for one (categorical-free) tree: the
    feature lookup is a one-hot contraction on the MXU (exact f32 via
    Precision.HIGHEST — a bf16-rounded value could flip a near-threshold
    decision) and the leaf resolution is a satisfied-condition count
    against the host-built path matrices.  Replaces the depth-deep
    gather loop of :func:`_walk_raw`, which is ~1000x slower on TPU
    (per-row gathers are the slow primitive; matmuls are free)."""
    # NaNs poison a one-hot contraction (0 * NaN = NaN), so the values
    # ride sanitized and the NaN indicator takes its own exact 0/1 matmul
    P = _onehot_feature_lookup(jnp.nan_to_num(X), split_feature)
    isn = _onehot_feature_lookup(jnp.isnan(X).astype(jnp.float32),
                                 split_feature) > 0.5
    dt = decision_type
    dleft = (dt & DEFAULT_LEFT_MASK) != 0
    miss_nan = (dt & (3 << 2)) == MISSING_NAN
    # P is already 0.0 at NaN cells (nan_to_num upstream), which is the
    # non-miss_nan fallback value; miss_nan nodes take default_left.
    # 0/1 decisions and +-1 path directions are bf16-exact; the S matmul
    # accumulates in f32, so the equality test stays exact
    dec = jnp.where(isn & miss_nan[None, :], dleft[None, :],
                    P <= threshold[None, :]).astype(jnp.bfloat16)
    # S counts satisfied path conditions: 0/1 x (+-1) products are
    # bf16-exact and the f32 accumulation of <=Nn terms is exact, so the
    # equality test below is safe at default matmul precision
    S = jax.lax.dot_general(dec, path_dir.astype(jnp.bfloat16),
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) + \
        plen_right[None, :]
    hit = S == plen_total[None, :]                              # (N, L)
    out = jnp.sum(jnp.where(hit, leaf_value[None, :], 0.0), axis=1)
    if not want_leaf:
        return out, None
    leaf = jnp.argmax(hit, axis=1).astype(jnp.int32)
    return out, leaf


def _linear_leaf_eval(X, val, leaf, lin_fields):
    """Linear-leaf evaluation with the NaN fallback (tree.cpp
    PredictionFunLinear) — shared by the dense and sequential walks."""
    lconst, lcoef, lfeat, lfmask, lflag = lin_fields
    rf = lfeat[leaf]
    rm = lfmask[leaf]
    vals = jnp.take_along_axis(X, rf, axis=1)
    nan_row = jnp.any(jnp.isnan(vals) & (rm > 0), axis=1)
    vals = jnp.where(rm > 0, jnp.nan_to_num(vals), 0.0)
    lin = lconst[leaf] + jnp.sum(lcoef[leaf] * vals, axis=1)
    use_lin = (lflag > 0) & jnp.logical_not(nan_row)
    return jnp.where(use_lin, lin, val)


@functools.partial(jax.jit, static_argnames=("has_linear",))
def _predict_dense_scan(X, fields, lin_fields=None, has_linear=False):
    """Jitted tree-scan over the dense walk (one compiled program per
    (shape, tree-count) instead of per-op eager dispatch)."""
    if not has_linear:
        def body(carry, tf):
            return carry + _walk_raw_dense(X, *tf, want_leaf=False)[0], None
        out, _ = jax.lax.scan(body, jnp.zeros((X.shape[0],), jnp.float32),
                              fields)
        return out

    def body_lin(carry, tf):
        tree_fields, lf = tf
        val, leaf = _walk_raw_dense(X, *tree_fields)
        return carry + _linear_leaf_eval(X, val, leaf, lf), None

    out, _ = jax.lax.scan(body_lin, jnp.zeros((X.shape[0],), jnp.float32),
                          (fields, lin_fields))
    return out


@functools.partial(jax.jit, static_argnames=("has_linear",))
def _predict_seq_scan(X, fields, lin_fields=None, has_linear=False):
    """Jitted tree-scan over the sequential raw walk (categorical
    ensembles) — the seq counterpart of :func:`_predict_dense_scan`, so
    the categorical inference path also compiles once per shape."""
    if not has_linear:
        def body(carry, tf):
            return carry + _walk_raw(X, *tf)[0], None
        out, _ = jax.lax.scan(body, jnp.zeros((X.shape[0],), jnp.float32),
                              fields)
        return out

    def body_lin(carry, tf):
        tree_fields, lf = tf
        val, leaf = _walk_raw(X, *tree_fields)
        return carry + _linear_leaf_eval(X, val, leaf, lf), None

    out, _ = jax.lax.scan(body_lin, jnp.zeros((X.shape[0],), jnp.float32),
                          (fields, lin_fields))
    return out


def ensemble_serve_fields(batch: TreeBatch, start: int = 0,
                          end: Optional[int] = None):
    """Pure-array view of one ensemble for :func:`predict_raw_ensemble`:
    ``(kind, fields, lin_fields)`` where ``kind`` is a static dispatch tag
    and the arrays are plain device-residable jnp arrays.  Because the
    jitted entry takes the arrays as ARGUMENTS, XLA's compile cache keys
    on shapes/dtypes only — two models with the same shape signature
    (tree count, leaves, features) share every compiled program."""
    t1 = batch.num_trees if end is None else min(end, batch.num_trees)
    t0 = min(start, t1)
    if batch.max_leaves <= 1:
        return "const", (batch.leaf_value[t0:t1],), None
    lin = None
    if batch.has_linear:
        lin = tuple(a[t0:t1] for a in
                    (batch.leaf_const, batch.leaf_coef, batch.leaf_feat,
                     batch.leaf_fmask, batch.linear_flag))
    if not batch.has_cat:
        fields = tuple(a[t0:t1] for a in
                       (batch.split_feature, batch.threshold,
                        batch.decision_type, batch.path_dir,
                        batch.plen_right, batch.plen_total,
                        batch.leaf_value))
        return ("dense_lin" if lin is not None else "dense"), fields, lin
    fields = tuple(a[t0:t1] for a in
                   (batch.split_feature, batch.threshold, batch.cat_words,
                    batch.decision_type, batch.left_child,
                    batch.right_child, batch.leaf_value, batch.num_leaves))
    return ("seq_lin" if lin is not None else "seq"), fields, lin


@functools.partial(jax.jit, static_argnames=("kinds",))
def predict_raw_ensemble(X, per_class, kinds):
    """Pure jitted ensemble prediction entry for the serving layer:
    ``per_class`` is a tuple over model classes of ``(fields,
    lin_fields)`` from :func:`ensemble_serve_fields`, ``kinds`` the
    matching static tags.  Returns (N, k) raw scores.  Module-level and
    argument-driven so every model with the same shape signature reuses
    one compiled program per row bucket."""
    cols = []
    for (fields, lin), kind in zip(per_class, kinds):
        if kind == "const":
            cols.append(jnp.broadcast_to(
                jnp.sum(fields[0]).astype(jnp.float32), (X.shape[0],)))
        elif kind == "dense":
            cols.append(_predict_dense_scan(X, fields))
        elif kind == "dense_lin":
            cols.append(_predict_dense_scan(X, fields, lin, has_linear=True))
        elif kind == "seq":
            cols.append(_predict_seq_scan(X, fields))
        elif kind == "seq_lin":
            cols.append(_predict_seq_scan(X, fields, lin, has_linear=True))
        else:
            raise ValueError(f"unknown ensemble kind: {kind}")
    return jnp.stack(cols, axis=1)


def predict_raw(batch: TreeBatch, X: jnp.ndarray,
                start_iteration: int = 0,
                num_iteration: Optional[int] = None) -> jnp.ndarray:
    """Ensemble raw-score prediction on raw features
    (reference gbdt_prediction.cpp:PredictRaw; linear-leaf evaluation per
    tree.cpp PredictionFunLinear with NaN fallback).  Categorical-free
    ensembles take the dense MXU walk; categorical trees keep the
    sequential walk (their bitset membership is a per-row gather)."""
    t_end = batch.num_trees if num_iteration is None else min(
        start_iteration + num_iteration, batch.num_trees)
    if batch.max_leaves <= 1:
        # all-stump ensemble: the prediction is the constants' sum (the
        # walks' node arrays are empty at ml == 1)
        const = jnp.sum(batch.leaf_value[start_iteration:t_end, 0])
        return jnp.full((X.shape[0],), const, jnp.float32)
    dense = not batch.has_cat
    if dense:
        fields = (batch.split_feature, batch.threshold,
                  batch.decision_type, batch.path_dir, batch.plen_right,
                  batch.plen_total, batch.leaf_value)
        sliced = tuple(a[start_iteration:t_end] for a in fields)
        if not batch.has_linear:
            return _predict_dense_scan(X, sliced)
        lin_sliced = tuple(
            a[start_iteration:t_end] for a in
            (batch.leaf_const, batch.leaf_coef, batch.leaf_feat,
             batch.leaf_fmask, batch.linear_flag))
        return _predict_dense_scan(X, sliced, lin_sliced, has_linear=True)
    fields = (batch.split_feature, batch.threshold, batch.cat_words,
              batch.decision_type, batch.left_child,
              batch.right_child, batch.leaf_value, batch.num_leaves)
    sliced = tuple(a[start_iteration:t_end] for a in fields)
    if not batch.has_linear:
        return _predict_seq_scan(X, sliced)
    lin_fields = tuple(a[start_iteration:t_end] for a in
                       (batch.leaf_const, batch.leaf_coef, batch.leaf_feat,
                        batch.leaf_fmask, batch.linear_flag))
    return _predict_seq_scan(X, sliced, lin_fields, has_linear=True)
