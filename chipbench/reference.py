"""The plain reference of a boosted-tree training run, and the comparison
that decides ``correct``.

It imports nothing of the program and takes nothing the program made except
the answer under test: the model text (LightGBM ``version=v3``), the
held-out predictions and a sample of the final training scores.  From the
seed it makes the rows again, block by block, walks them through the
program's splits in float64, and recomputes in float64 everything the
program states about its first trees:

* ``leaf_count_diff``  rows per leaf (binning, thresholds, row update): exact
* ``leaf_value_gap``   leaf outputs from the reference's own gradients of its
                       own scores (gradients, histogram sums or leaf renewal,
                       shrinkage, score update between trees)
* ``split_gain_gap``   the gain of every split, worst node (histogram sums, the
                       split scan's arithmetic, rows left out)
* ``split_gain_median_gap``  the same at the median node of the worst tree
                       (the precision of the histogram arithmetic)
* ``train_score_gap``  the program's final training scores on sampled row
                       blocks against the sum of ALL its trees (row update and
                       score update of every tree the window grew)
* ``heldout_pred_gap`` the program's predictions on the held-out rows against
                       the float64 walk of the same trees (the dense predictor)

Gaps are measured per leaf (node, row) against the reference's value or the
median one, whichever is larger, and the worst is reported.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen

FOLLOWED_TREES = 3           # the reference follows the first three steps


class Tree:
    """One tree of a LightGBM v3 model text, numerical splits only."""

    def __init__(self, fields: dict):
        def arr(key, dtype):
            return np.array(fields.get(key, "").split(), dtype=dtype)
        self.num_leaves = int(fields["num_leaves"])
        if int(fields.get("num_cat", 0)) != 0:
            raise ValueError("reference walks numerical splits only")
        self.split_feature = arr("split_feature", np.int64)
        self.split_gain = arr("split_gain", np.float64)
        self.threshold = arr("threshold", np.float64)
        self.decision_type = arr("decision_type", np.int64)
        self.left = arr("left_child", np.int64)
        self.right = arr("right_child", np.int64)
        self.leaf_value = arr("leaf_value", np.float64)
        self.leaf_count = arr("leaf_count", np.int64)
        if np.any(self.decision_type & 1):
            raise ValueError("reference walks numerical splits only")
        n_int = self.num_leaves - 1
        for name in ("split_feature", "split_gain", "threshold", "left", "right"):
            if len(getattr(self, name)) != n_int:
                raise ValueError(f"tree field {name}: {len(getattr(self, name))} "
                                 f"entries for {n_int} splits")
        if len(self.leaf_value) != self.num_leaves:
            raise ValueError("tree field leaf_value: wrong length")

    def walk(self, x: np.ndarray) -> np.ndarray:
        """Leaf index of every row of ``x`` (n, F): left iff value <= threshold,
        compared in float64.  The data has no missing values."""
        n = x.shape[0]
        if self.num_leaves == 1:
            return np.zeros(n, np.int32)
        node = np.zeros(n, np.int64)
        idx = np.arange(n)
        while idx.size:
            nd = node[idx]
            v = x[idx, self.split_feature[nd]].astype(np.float64)
            nxt = np.where(v <= self.threshold[nd], self.left[nd], self.right[nd])
            node[idx] = nxt
            idx = idx[nxt >= 0]
        return (~node).astype(np.int32)

    def children_sums(self, leaf_sums: np.ndarray) -> np.ndarray:
        """Per internal node, the sum of ``leaf_sums`` over the leaves below
        it.  Children carry larger indices than their parent."""
        out = np.zeros(self.num_leaves - 1, leaf_sums.dtype)
        for i in range(self.num_leaves - 2, -1, -1):
            for c in (self.left[i], self.right[i]):
                out[i] += out[c] if c >= 0 else leaf_sums[~c]
        return out

    def side_sum(self, child: int, leaf_sums, node_sums):
        return node_sums[child] if child >= 0 else leaf_sums[~child]


def parse_model(text: str) -> list:
    """The trees of a LightGBM v3 model text, in order."""
    trees, cur = [], None
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("Tree="):
            cur = {}
            trees.append(cur)
        elif line.startswith("end of trees"):
            cur = None
        elif cur is not None and "=" in line:
            k, v = line.split("=", 1)
            cur[k] = v
    return [Tree(t) for t in trees]


def auc(y: np.ndarray, p: np.ndarray) -> float:
    """Area under the ROC curve, ties counted half (rank formula)."""
    y = np.asarray(y) > 0.5
    order = np.argsort(p, kind="mergesort")
    ps = np.asarray(p)[order]
    ranks = np.empty(len(ps), np.float64)
    # average ranks over ties
    bounds = np.flatnonzero(np.r_[True, ps[1:] != ps[:-1], True])
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        ranks[lo:hi] = 0.5 * (lo + hi - 1) + 1.0
    r = np.empty_like(ranks)
    r[order] = ranks
    n1 = int(y.sum())
    n0 = len(y) - n1
    if n1 == 0 or n0 == 0:
        raise ValueError("auc needs both classes")
    return float((r[y].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def _sigmoid(s):
    return 1.0 / (1.0 + np.exp(-s))


def _gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|got - ref| against max(|ref|, median |ref|), per leaf or node."""
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    return np.abs(got - ref) / scale


def _gap(got: np.ndarray, ref: np.ndarray) -> float:
    """The worst of :func:`_gaps`."""
    return float(np.max(_gaps(got, ref)))


def predict_raw(trees: list, x: np.ndarray, rounding=None) -> np.ndarray:
    """Float64 raw score: the sum of each tree's leaf value, by walking.
    ``rounding`` (the lower-precision control) rounds every leaf value and
    every partial sum."""
    out = np.zeros(x.shape[0], np.float64)
    for t in trees:
        add = t.leaf_value[t.walk(x)]
        out = out + add if rounding is None else rounding(out + rounding(add))
    return out


class Params:
    """What the reference needs of a configuration's ``params`` group."""

    def __init__(self, params: dict):
        if params.get("objective") != "binary":
            raise ValueError("reference follows the binary objective only")
        self.learning_rate = float(params["learning_rate"])
        self.lambda_l2 = float(params.get("lambda_l2", 0.0))


def walk_followed(spec: datagen.TabularSpec, seed: int, trees: list,
                  threads: int | None = None) -> tuple:
    """Make every training row again, block by block, and walk it through the
    first trees.  Returns (leaf ids int16 (k, rows), labels float64 (rows,))."""
    followed = trees[:FOLLOWED_TREES]
    w = datagen.weights(spec)
    leaf = np.empty((len(followed), spec.rows), np.int16)
    y = np.empty(spec.rows, np.float64)

    def one(b: int) -> None:
        lo, hi = spec.block_range(b)
        xb, yb = datagen.block(spec, seed, b, w)
        y[lo:hi] = yb
        for t, tree in enumerate(followed):
            leaf[t, lo:hi] = tree.walk(xb)

    with ThreadPoolExecutor(max_workers=threads or datagen.worker_threads()) as pool:
        list(pool.map(one, range(spec.blocks)))
    return leaf, y


def recompute(trees: list, leaf: np.ndarray, y: np.ndarray, params: Params, *,
              grad_round=None, keep=None) -> dict:
    """Leaf counts, leaf values and split gains of the followed trees from
    the reference's own scores, in float64.

    ``grad_round`` (the lower-precision control) rounds the gradient and
    hessian vectors before they are summed; ``keep`` (the planted fault) is a
    0/1 row vector: rows with 0 are left out of every sum.  Per tree:
    ``count``, ``out`` (shrunken output without the bias) and ``gain``;
    ``bias`` is the boost-from-average score."""
    pavg = min(max(float(y.mean()), 1e-15), 1.0 - 1e-15)
    bias = float(np.log(pavg / (1.0 - pavg)))
    score = np.full(len(y), bias, np.float64)
    l2 = params.lambda_l2
    res = {"bias": bias, "count": [], "out": [], "gain": [], "G": [], "H": []}
    for t in range(leaf.shape[0]):
        tree, lt = trees[t], leaf[t]
        p = _sigmoid(score)
        g, h = p - y, p * (1.0 - p)
        if grad_round is not None:
            g, h = grad_round(g), grad_round(h)
        if keep is not None:
            g, h = g * keep, h * keep
        L = tree.num_leaves
        G = np.bincount(lt, weights=g, minlength=L)
        H = np.bincount(lt, weights=h, minlength=L)
        res["count"].append(np.bincount(lt, weights=keep, minlength=L).astype(np.int64))
        nG, nH = tree.children_sums(G), tree.children_sums(H)
        lG = np.array([tree.side_sum(c, G, nG) for c in tree.left])
        lH = np.array([tree.side_sum(c, H, nH) for c in tree.left])
        rG, rH = nG - lG, nH - lH
        with np.errstate(invalid="ignore", divide="ignore"):   # a leaf a fault left empty
            out = -G / (H + l2) * params.learning_rate
            gain = lG * lG / (lH + l2) + rG * rG / (rH + l2) - nG * nG / (nH + l2)
        res["gain"].append(gain)
        res["G"].append(G)
        res["H"].append(H)
        res["out"].append(out)
        score += out[lt]
    return res


def stated(trees: list, bias: float) -> dict:
    """What the program's model text states of its first trees, in the shape
    of :func:`recompute`'s result."""
    k = min(FOLLOWED_TREES, len(trees))
    return {"count": [t.leaf_count for t in trees[:k]],
            "out": [t.leaf_value - (bias if i == 0 else 0.0) for i, t in enumerate(trees[:k])],
            "gain": [t.split_gain for t in trees[:k]]}


def compare_followed(got: dict, ref: dict) -> dict:
    """The three numbers of the followed trees: ``got`` (the program's stated
    values, or a control's) against the reference."""
    return {
        "leaf_count_diff": float(sum(int(np.abs(a - b).sum())
                                     for a, b in zip(got["count"], ref["count"]))),
        "leaf_value_gap": max(_gap(a, b) for a, b in zip(got["out"], ref["out"])),
        "split_gain_gap": max(_gap(a, b) for a, b in zip(got["gain"], ref["gain"])),
        # the median node of the worst tree: the splits the program commits from a row
        # subsample (the speculative ramp) state estimated gains, a tenth of the nodes
        # and the whole of the worst gap; the median node shows the arithmetic alone
        "split_gain_median_gap": max(float(np.median(_gaps(a, b)))
                                     for a, b in zip(got["gain"], ref["gain"])),
    }


def sample_blocks(spec: datagen.TabularSpec, seed: int, k: int) -> list:
    """``k`` row blocks drawn from the seed, the last (short) block never."""
    full = spec.rows // datagen.BLOCK_ROWS
    rng = np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(1_000_003,)))
    if full <= k:
        return list(range(max(full, 1)))
    return sorted(int(b) for b in rng.choice(full, size=k, replace=False))


def train_score_gap(spec, seed, trees: list, scores: dict) -> float:
    """Worst |program's final training score - sum of all its trees' leaf
    values| over the sampled blocks.  ``scores`` maps a block to the program's
    scores of its rows."""
    w = datagen.weights(spec)

    def one(b: int) -> float:
        xb, _ = datagen.block(spec, seed, b, w)
        want = predict_raw(trees, xb)
        got = np.asarray(scores[b], np.float64)
        if got.shape != want.shape:        # rows the program never scored
            return float("inf")
        return float(np.max(np.abs(got - want)))

    with ThreadPoolExecutor(max_workers=datagen.worker_threads()) as pool:
        return max(pool.map(one, sorted(scores)))


def heldout_pred_gap(trees: list, xh: np.ndarray, prob: np.ndarray) -> float:
    """Worst |program's predicted probability - sigmoid of the float64 walk|."""
    return float(np.max(np.abs(np.asarray(prob, np.float64) - _sigmoid(predict_raw(trees, xh)))))


def compare_run(spec, seed, params: Params, model_text: str, scores: dict,
                xh: np.ndarray, prob: np.ndarray, pred_trees: int) -> tuple:
    """Every number a run compares, from the answers the program gave: its
    model text, its final training scores on the sampled blocks, and its
    predictions on the held-out rows with the first ``pred_trees`` trees.
    Returns (numbers, trees, leaf ids, labels, the reference's sums)."""
    trees = parse_model(model_text)
    leaf, labels = walk_followed(spec, seed, trees)
    ref = recompute(trees, leaf, labels, params)
    numbers = compare_followed(stated(trees, ref["bias"]), ref)
    numbers["train_score_gap"] = train_score_gap(spec, seed, trees, scores)
    numbers["heldout_pred_gap"] = heldout_pred_gap(trees[:pred_trees], xh, prob)
    return numbers, trees, leaf, labels, ref


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit), ...]).  Every compared number needs a
    limit of its own; a number that is not finite fails."""
    rows, ok = [], True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit stated for compared number {name!r}")
        limit = float(limits[name])
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        rows.append((name, float(value), limit))
    return ok, rows


# ---- controls: the nearest precision below the one a configuration states ----

def round_bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (8 significant bits, nearest even), as float64."""
    u = np.asarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


ROUNDINGS = {"bf16": round_bf16}
