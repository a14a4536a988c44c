"""The plain reference of a ``boosting=goss`` run: :mod:`chipbench.reference`
carried past the trees that GOSS does not sample, to the first sampled ones.

GOSS (Ke et al., NeurIPS 2017, Algorithm 2; ``src/boosting/goss.hpp``) sees
every row for the first ``1/learning_rate`` trees.  From then on a tree keeps
the ``top_rate`` share of the rows with the largest ``|g*h|``, keeps each
other row independently with probability ``other_rate / (1 - top_rate)``, and
multiplies the kept other rows' gradients and hessians by
``(1 - top_rate) / other_rate``.  Beside the answers the other cells give
(model text, final training scores on sampled blocks, held-out predictions)
this reference takes the program's per-row CLASS of each followed sampled
tree: 0 out of the bag, 1 kept at weight 1, 2 kept at the multiplier.  It
imports nothing of the program.  With ``W`` unsampled trees before:

* its scores before tree ``W+1`` are the program's stated leaf values of
  trees 1..W along its OWN float64 walk of every row (those trees are not
  recomputed here: the cell without sampling follows them on the same path);
* per followed sampled tree it computes ``g``, ``h`` and ``|g*h|`` in float64
  from its own scores and holds the stated classes to the sample's law:

  ``goss_top_violations``  rows whose class disagrees with the reference's own
      ranking by more than a relative margin in ``|g*h|`` around its own k-th
      largest value (a class-1 row clearly below, a class-0 or -2 row clearly
      above): an approximate threshold shows here
  ``goss_top_share_gap``   ``|#class 1 / N - top_rate|`` (ties at the threshold
      are kept, so the count may pass ``int(N * top_rate)``)
  ``goss_rest_rate_gap``   ``|#class 2 / #(not class 1) - other_rate/(1-top_rate)|``
  ``goss_rest_bias``       kept share of the other rows above their median
      ``|g*h|`` minus the kept share below it: a draw that favours large
      gradients shows here

* it recomputes the tree's leaf counts (rows of class 1 or 2), leaf values
  and split gains from its own gradients times the weights 0 / 1 /
  ``(1-top_rate)/other_rate`` that IT derives from the class (the
  amplification is the configuration's guarantee, not the program's say),
  compares them as :func:`chipbench.reference.compare_followed` does, and
  moves its scores on by its own outputs;
* ``train_score_gap`` over ALL trees and ``heldout_pred_gap`` are
  :mod:`chipbench.reference`'s own functions.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen, reference

OUT, TOP, REST = 0, 1, 2     # a row's class in a sampled tree
TOP_MARGIN = 1e-4            # relative, around the reference's own k-th largest |g*h|


class Params(reference.Params):
    """What this reference needs of a configuration's ``params`` group."""

    def __init__(self, params: dict):
        super().__init__(params)
        if params.get("boosting") != "goss":
            raise ValueError("reference_goss follows boosting=goss only")
        self.top_rate = float(params["top_rate"])
        self.other_rate = float(params["other_rate"])
        if not (self.top_rate > 0.0 and self.other_rate > 0.0
                and self.top_rate + self.other_rate < 1.0):
            raise ValueError("goss: top_rate, other_rate > 0 and their sum < 1")

    @property
    def rest_rate(self) -> float:
        return self.other_rate / (1.0 - self.top_rate)

    @property
    def amplification(self) -> float:
        return (1.0 - self.top_rate) / self.other_rate

    @property
    def unsampled_trees(self) -> int:
        return int(1.0 / self.learning_rate)


def walk_trees(spec: datagen.TabularSpec, seed: int, trees: list,
               threads: int | None = None) -> tuple:
    """Make every training row again, block by block, and walk it through
    ``trees``.  Returns (leaf ids int16 (len(trees), rows), labels float64)."""
    w = datagen.weights(spec)
    leaf = np.empty((len(trees), spec.rows), np.int16)
    y = np.empty(spec.rows, np.float64)

    def one(b: int) -> None:
        lo, hi = spec.block_range(b)
        xb, yb = datagen.block(spec, seed, b, w)
        y[lo:hi] = yb
        for t, tree in enumerate(trees):
            leaf[t, lo:hi] = tree.walk(xb)

    with ThreadPoolExecutor(max_workers=threads or datagen.worker_threads()) as pool:
        list(pool.map(one, range(spec.blocks)))
    return leaf, y


def sample_law(cls: np.ndarray, s: np.ndarray, params: Params) -> dict:
    """The four numbers of one sampled tree's classes against the reference's
    own ``s = |g*h|``."""
    n = len(s)
    k = max(1, int(n * params.top_rate))
    kth = float(np.partition(s, n - k)[n - k])
    top = cls == TOP
    below = int(np.count_nonzero(top & (s < kth * (1.0 - TOP_MARGIN))))
    above = int(np.count_nonzero(~top & (s > kth * (1.0 + TOP_MARGIN))))
    law = {"goss_top_violations": float(below + above),
           "goss_top_share_gap": abs(np.count_nonzero(top) / n - params.top_rate),
           "goss_rest_rate_gap": float("inf"), "goss_rest_bias": float("inf")}
    rest = ~top
    if rest.any():
        s_rest, kept_rest = s[rest], cls[rest] == REST
        upper = s_rest > np.median(s_rest)
        share = lambda sel: np.count_nonzero(kept_rest & sel) / max(1, np.count_nonzero(sel))
        law["goss_rest_rate_gap"] = abs(kept_rest.mean() - params.rest_rate)
        law["goss_rest_bias"] = abs(share(upper) - share(~upper))
    return law


def weighted_tree(tree, lt: np.ndarray, g: np.ndarray, h: np.ndarray, in_bag: np.ndarray,
                  params: Params) -> dict:
    """Leaf counts, shrunken leaf outputs and split gains of one tree from
    weighted gradients, as :func:`chipbench.reference.recompute` computes them."""
    L, l2 = tree.num_leaves, params.lambda_l2
    G = np.bincount(lt, weights=g, minlength=L)
    H = np.bincount(lt, weights=h, minlength=L)
    count = np.bincount(lt, weights=in_bag, minlength=L).astype(np.int64)
    nG, nH = tree.children_sums(G), tree.children_sums(H)
    lG = np.array([tree.side_sum(c, G, nG) for c in tree.left])
    lH = np.array([tree.side_sum(c, H, nH) for c in tree.left])
    rG, rH = nG - lG, nH - lH
    with np.errstate(invalid="ignore", divide="ignore"):       # a leaf a fault left empty
        out = -G / (H + l2) * params.learning_rate
        gain = lG * lG / (lH + l2) + rG * rG / (rH + l2) - nG * nG / (nH + l2)
    return {"count": count, "out": out, "gain": gain}


def follow_sampled(trees: list, leaf: np.ndarray, y: np.ndarray, params: Params,
                   classes: list, unsampled: int, *, amplification: float | None = None,
                   alter=None) -> dict:
    """The eight numbers of the followed sampled trees ``unsampled ..
    unsampled + len(classes)``.  ``leaf`` holds the walk of trees
    ``0 .. unsampled + len(classes)``.  The planted faults: ``amplification``
    replaces the configuration's multiplier in the reference's own weights;
    ``alter(cls, s)`` replaces a tree's stated classes, given the reference's
    own ``|g*h|``."""
    amp = params.amplification if amplification is None else float(amplification)
    # tree 1's stated leaf values carry the boost-from-average score
    score = np.zeros(len(y), np.float64)
    for t in range(unsampled):
        score += trees[t].leaf_value[leaf[t]]
    law: dict = {}
    got = {"count": [], "out": [], "gain": []}
    ref = {"count": [], "out": [], "gain": []}
    for i, cls in enumerate(classes):
        t = unsampled + i
        tree, lt = trees[t], leaf[t]
        p = reference._sigmoid(score)
        g, h = p - y, p * (1.0 - p)
        s = np.abs(g * h)
        cls = np.asarray(cls)
        if cls.shape != s.shape:
            raise ValueError(f"tree {t + 1}: {cls.shape} classes for {s.shape} rows")
        if alter is not None:
            cls = alter(cls, s)
        for name, value in sample_law(cls, s, params).items():
            law[name] = max(law.get(name, 0.0), float(value))
        weight = np.where(cls == REST, amp, (cls == TOP).astype(np.float64))
        mine = weighted_tree(tree, lt, g * weight, h * weight, (cls != OUT).astype(np.float64),
                             params)
        for key in ref:
            ref[key].append(mine[key])
        got["count"].append(tree.leaf_count)
        got["out"].append(tree.leaf_value)
        got["gain"].append(tree.split_gain)
        score += mine["out"][lt]
    return dict(law, **reference.compare_followed(got, ref))


def compare_run(spec, seed, params: Params, model_text: str, classes: list, unsampled: int,
                scores: dict, xh: np.ndarray, prob: np.ndarray, pred_trees: int) -> tuple:
    """Every number a ``goss`` run compares, from the answers the program
    gave.  Returns (numbers, trees, leaf ids, labels)."""
    trees = reference.parse_model(model_text)
    followed = unsampled + len(classes)
    if len(trees) < followed:
        raise ValueError(f"model has {len(trees)} trees; {followed} are followed")
    if unsampled != params.unsampled_trees:
        raise ValueError(f"the mix states {unsampled} unsampled trees; learning_rate "
                         f"{params.learning_rate} gives {params.unsampled_trees}")
    leaf, labels = walk_trees(spec, seed, trees[:followed])
    numbers = follow_sampled(trees, leaf, labels, params, classes, unsampled)
    numbers["train_score_gap"] = reference.train_score_gap(spec, seed, trees, scores)
    numbers["heldout_pred_gap"] = reference.heldout_pred_gap(trees[:pred_trees], xh, prob)
    return numbers, trees, leaf, labels


# ---- planted faults: what a wrong sampler's classes would look like ----

def redraw_rest(rate: float, seed: int):
    """``alter``: the rows outside the top set kept anew at ``rate``."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(2_000_003,)))

    def alter(cls, s):
        out = np.where(cls == TOP, TOP, OUT).astype(np.uint8)
        out[(cls != TOP) & (rng.random(len(cls)) < rate)] = REST
        return out
    return alter


def favour_large(rate: float, seed: int):
    """``alter``: the rows outside the top set kept anew with a probability
    in proportion to their ``|g*h|`` (``rate`` on average): the draw of an
    importance sampler, which the amplification by a constant does not undo."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(2_000_005,)))

    def alter(cls, s):
        rest = cls != TOP
        p = np.minimum(1.0, rate * s / s[rest].mean())
        return np.where(rest, np.where(rng.random(len(cls)) < p, REST, OUT), TOP).astype(np.uint8)
    return alter


def subsample_threshold(params: Params, stride: int = 64):
    """``alter``: the top set cut at the k-th largest of every ``stride``-th
    row, what an approximate quantile gives; the other rows keep their draw
    where they had one and take their neighbour's where they had none."""
    def alter(cls, s):
        sub = s[::stride]
        k = max(1, int(len(sub) * params.top_rate))
        top = s >= np.partition(sub, len(sub) - k)[len(sub) - k]
        kept = np.where(cls == TOP, np.roll(cls, 1) == REST, cls == REST)
        return np.where(top, TOP, np.where(kept, REST, OUT)).astype(np.uint8)
    return alter
