"""The plain reference of a training run on integer + categorical columns
with missing values, and the comparison that decides ``correct`` there.

Like ``reference.py`` it imports nothing of the program and takes of it only
the answers under test: the model text (LightGBM ``version=v3``), the
held-out predictions, a sample of the final training scores.  It parses the
model text in full — ``decision_type`` bits (bit 0 categorical, bit 1
default left, bits 2-3 the missing type), ``cat_boundaries`` /
``cat_threshold`` (a node's left set as a bitset over RAW category values),
``feature_infos`` (a categorical column's binned categories) — makes every
training row again from the seed, block by block, walks all of them through
the program's splits ON RAW VALUES in float64, and recomputes the first three
trees from its own scores.  Beside ``reference.py``'s six numbers:

* ``cat_law_violations``  over the categorical nodes of the followed trees: a
      left set of more than ``max_cat_threshold`` categories (one where the
      column has at most ``max_cat_to_onehot`` bins), a child with fewer than
      ``min_data_in_leaf`` rows, a category in a left set that is none of the
      column's binned categories.  Exact: limit 0.
* ``cat_search_gap``  for every categorical node of the followed trees the
      reference histograms the node's rows by the binned categories of the
      feature the program chose and runs the published search itself, in
      float64; the number is the worst relative shortfall of the program's
      stated gain against the reference's own best.

The walk (``Tree::Decision``): a numerical node sends a row left iff
``value <= threshold``; a NaN follows the node's default direction where its
missing type is NaN and counts as 0.0 otherwise.  A categorical node
(``Tree::CategoricalDecision``) sends a row left iff its value is a
non-negative integer whose bit is set; NaN, negative and unknown values go
right.

The search (``feature_histogram.hpp`` ``FindBestThresholdCategoricalInner``),
and where this file departs from the published description:

* bin 0 (no category: missing, folded away, unseen) is never a candidate;
* at most ``max_cat_to_onehot`` bins: each category against the rest, plain
  ``lambda_l2``; else the categories with at least ``cat_smooth`` rows are
  ordered by ``sum_grad / (sum_hess + cat_smooth)`` and scanned from both
  ends, at most ``min(max_cat_threshold, (used + 1) / 2)`` of them,
  ``lambda_l2 + cat_l2`` in the children's gains (the parent's keeps the plain
  ``lambda_l2``), a position evaluated only once ``min_data_per_group`` rows
  came in since the last evaluated one, the scan ended where the other side
  falls under ``min_data_in_leaf`` or ``min_data_per_group``;
* DEPARTURE: counts are the exact row counts (the published code estimates a
  bin's count from its hessian sum), and ``kEpsilon`` is left out;
* DEPARTURE: the gain of every node is recomputed from the rows the PROGRAM's
  split sends each way (``split_gain_gap``), so the program's own
  approximation of the group rule (it evaluates a position where the prefix
  count crosses a multiple of ``min_data_per_group``) shows only in
  ``cat_search_gap``, as a shortfall or none.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen_ctr
from .reference import FOLLOWED_TREES, _sigmoid, compare_followed, predict_raw, stated
from .reference import Tree as _NumericTree

CAT, DEFAULT_LEFT, MISSING_NAN = 1, 2, 2     # decision_type: bit 0, bit 1, bits 2-3 == 2


class Tree(_NumericTree):
    """One tree of a LightGBM v3 model text, numerical and categorical splits."""

    def __init__(self, fields: dict):
        def arr(key, dtype):
            return np.array(fields.get(key, "").split(), dtype=dtype)
        self.num_leaves = int(fields["num_leaves"])
        self.split_feature = arr("split_feature", np.int64)
        self.split_gain = arr("split_gain", np.float64)
        self.threshold = arr("threshold", np.float64)
        self.decision_type = arr("decision_type", np.int64)
        self.left = arr("left_child", np.int64)
        self.right = arr("right_child", np.int64)
        self.leaf_value = arr("leaf_value", np.float64)
        self.leaf_count = arr("leaf_count", np.int64)
        n_int = self.num_leaves - 1
        for name in ("split_feature", "split_gain", "threshold", "decision_type", "left",
                     "right"):
            if len(getattr(self, name)) != n_int:
                raise ValueError(f"tree field {name}: {len(getattr(self, name))} "
                                 f"entries for {n_int} splits")
        if len(self.leaf_value) != self.num_leaves:
            raise ValueError("tree field leaf_value: wrong length")
        self.is_cat = (self.decision_type & CAT) != 0
        self.default_left = (self.decision_type & DEFAULT_LEFT) != 0
        self.missing_nan = ((self.decision_type >> 2) & 3) == MISSING_NAN
        bounds = arr("cat_boundaries", np.int64)
        words = arr("cat_threshold", np.uint64)
        if int(fields.get("num_cat", 0)) != max(len(bounds) - 1, 0) or \
                int(self.is_cat.sum()) != max(len(bounds) - 1, 0):
            raise ValueError("tree: num_cat, cat_boundaries and decision_type disagree")
        # left_sets[node]: sorted raw category values (None on a numerical node)
        self.left_sets = [None] * n_int
        for i in np.flatnonzero(self.is_cat):
            r = int(self.threshold[i])
            ws = words[bounds[r]:bounds[r + 1]].astype("<u4")
            # bit b of word k is raw category 32 k + b
            self.left_sets[i] = np.flatnonzero(
                np.unpackbits(ws.view(np.uint8), bitorder="little")).astype(np.int64)
        self._member = None

    def member(self) -> tuple:
        """``(table, row of node)``: a bool table (categorical nodes, ids) of the
        left sets, for the vector walk."""
        if self._member is None:
            nodes = np.flatnonzero(self.is_cat)
            width = 1 + max([int(s.max()) for s in self.left_sets if s is not None and len(s)],
                            default=0)
            table = np.zeros((max(len(nodes), 1), width), bool)
            row = np.zeros(max(self.num_leaves - 1, 1), np.int64)
            for k, i in enumerate(nodes):
                table[k, self.left_sets[i]] = True
                row[i] = k
            self._member = (table, row)
        return self._member

    def goes_left(self, nd: np.ndarray, v: np.ndarray, unknown_as=None) -> np.ndarray:
        """Each row's decision at its node ``nd`` on its raw value ``v``
        (float64).  ``unknown_as`` (a planted fault): per feature ``(known ids
        bool table, stand-in id)``; a NaN or unknown category is walked as the
        stand-in."""
        nan = np.isnan(v)
        num = np.where(nan & self.missing_nan[nd], self.default_left[nd],
                       np.where(nan, 0.0, v) <= self.threshold[nd])
        if not self.is_cat.any():
            return num
        cat = self.is_cat[nd]
        table, row = self.member()
        with np.errstate(invalid="ignore"):
            iv = np.where(nan | (v < 0) | (v >= 2 ** 31), -1, v).astype(np.int64)
        whole = (iv >= 0) & (iv == v)
        if unknown_as is not None:
            f = self.split_feature[nd]
            for feat, (known, stand_in) in unknown_as.items():
                here = cat & (f == feat)
                bad = here & ~(whole & (iv < len(known)) & known[np.clip(iv, 0, len(known) - 1)])
                iv = np.where(bad, stand_in, iv)
                whole = whole | bad
        inside = whole & (iv < table.shape[1])
        go = inside & table[row[nd], np.clip(iv, 0, table.shape[1] - 1)]
        return np.where(cat, go, num)

    def walk(self, x: np.ndarray, unknown_as=None) -> np.ndarray:
        """Leaf index of every row of ``x`` (n, F), on raw values."""
        n = x.shape[0]
        if self.num_leaves == 1:
            return np.zeros(n, np.int32)
        node = np.zeros(n, np.int64)
        idx = np.arange(n)
        while idx.size:
            nd = node[idx]
            v = x[idx, self.split_feature[nd]].astype(np.float64)
            nxt = np.where(self.goes_left(nd, v, unknown_as), self.left[nd], self.right[nd])
            node[idx] = nxt
            idx = idx[nxt >= 0]
        return (~node).astype(np.int32)

    def leaf_ranges(self) -> tuple:
        """``(rank of leaf in depth-first order, per internal node (first, end)
        of the ranks below it)``: a node's leaves are a range of ranks."""
        rank = np.zeros(self.num_leaves, np.int64)
        span = np.zeros((max(self.num_leaves - 1, 1), 2), np.int64)
        nxt = 0
        stack = [(0, False)] if self.num_leaves > 1 else []
        while stack:
            c, done = stack.pop()
            if c < 0:
                rank[~c] = nxt
                nxt += 1
            elif done:
                span[c, 1] = nxt
            else:
                span[c, 0] = nxt
                stack += [(c, True), (self.right[c], False), (self.left[c], False)]
        return rank, span


def parse_model(text: str) -> tuple:
    """``(trees in order, feature_infos entries)`` of a LightGBM v3 model text."""
    trees, cur, infos = [], None, []
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
        elif not trees and line.startswith("feature_infos="):
            infos = line.split("=", 1)[1].split()
    return [Tree(t) for t in trees], infos


def binned_categories(infos: list, feature: int) -> np.ndarray:
    """The raw categories of ``feature``'s bins 1.. as ``feature_infos``
    lists them (its first entry, -1, is bin 0: no category)."""
    entry = infos[feature]
    if entry.startswith("[") or entry == "none":
        raise ValueError(f"feature {feature} is not categorical in feature_infos: {entry!r}")
    cats = np.array([int(c) for c in entry.split(":")], np.int64)
    return cats[cats >= 0]


class Params:
    """What the reference needs of a configuration's ``params`` group."""

    def __init__(self, params: dict):
        if params.get("objective") != "binary":
            raise ValueError("reference follows the binary objective only")
        self.learning_rate = float(params["learning_rate"])
        self.lambda_l2 = float(params.get("lambda_l2", 0.0))
        self.min_data_in_leaf = int(params.get("min_data_in_leaf", 20))
        self.min_sum_hessian = float(params.get("min_sum_hessian_in_leaf", 1e-3))
        self.min_gain_to_split = float(params.get("min_gain_to_split", 0.0))
        self.cat_l2 = float(params.get("cat_l2", 10.0))
        self.cat_smooth = float(params.get("cat_smooth", 10.0))
        self.max_cat_to_onehot = int(params.get("max_cat_to_onehot", 4))
        self.max_cat_threshold = int(params.get("max_cat_threshold", 32))
        self.min_data_per_group = int(params.get("min_data_per_group", 100))


def _leaf_gain(g, h, l2):
    return g * g / (h + l2)


def search(hg, hh, hc, p: Params, onehot_only: bool = False) -> tuple:
    """The published categorical search on one node's histogram over the
    feature's bins (index 0: no category; float64 sums, exact counts).
    Returns ``(best gain above the parent's, left bins)``; ``(-inf, [])``
    where no split qualifies.  ``onehot_only`` (a planted fault): each
    category against the rest whatever the number of bins."""
    G, H, n = hg.sum(), hh.sum(), hc.sum()
    shift = _leaf_gain(G, H, p.lambda_l2) + p.min_gain_to_split
    best, best_set = -np.inf, []
    bins = np.arange(1, len(hg))
    if len(hg) <= p.max_cat_to_onehot or onehot_only:
        for t in bins:
            if hc[t] < p.min_data_in_leaf or hh[t] < p.min_sum_hessian:
                continue
            if n - hc[t] < p.min_data_in_leaf or H - hh[t] < p.min_sum_hessian:
                continue
            gain = _leaf_gain(G - hg[t], H - hh[t], p.lambda_l2) + \
                _leaf_gain(hg[t], hh[t], p.lambda_l2)
            if gain > shift and gain > best:
                best, best_set = gain, [int(t)]
        return best - shift, best_set
    used = bins[hc[bins] >= p.cat_smooth]
    used = used[np.argsort(hg[used] / (hh[used] + p.cat_smooth), kind="stable")]
    l2 = p.lambda_l2 + p.cat_l2
    most = min(p.max_cat_threshold, (len(used) + 1) // 2)
    for seq in (used, used[::-1]):
        lg = lh = lc = 0.0
        group = 0.0
        for i in range(min(len(seq), most)):
            t = seq[i]
            lg, lh, lc, group = lg + hg[t], lh + hh[t], lc + hc[t], group + hc[t]
            if lc < p.min_data_in_leaf or lh < p.min_sum_hessian:
                continue
            if n - lc < p.min_data_in_leaf or n - lc < p.min_data_per_group or \
                    H - lh < p.min_sum_hessian:
                break
            if group < p.min_data_per_group:
                continue
            group = 0.0
            gain = _leaf_gain(lg, lh, l2) + _leaf_gain(G - lg, H - lh, l2)
            if gain > shift and gain > best:
                best, best_set = gain, [int(b) for b in seq[:i + 1]]
    return best - shift, best_set


def cat_features(trees: list) -> list:
    """The features that categorical nodes of the followed trees split on."""
    return sorted({int(f) for t in trees[:FOLLOWED_TREES]
                   for f in t.split_feature[t.is_cat]})


def walk_followed(spec, seed: int, trees: list, keep: list, threads: int | None = None,
                  unknown_as=None) -> tuple:
    """Make every training row again, block by block, and walk it through the
    first trees.  Returns (leaf ids int16 (k, rows), labels float64 (rows,),
    {feature: its raw categories int32 (rows,), -1 where missing} for the
    features of ``keep``)."""
    followed = trees[:FOLLOWED_TREES]
    tables = datagen_ctr.Tables(spec)
    leaf = np.empty((len(followed), spec.rows), np.int16)
    y = np.empty(spec.rows, np.float64)
    cols = {f: np.empty(spec.rows, np.int32) for f in keep}

    def one(b: int) -> None:
        lo, hi = spec.block_range(b)
        xb, yb = datagen_ctr.block(spec, seed, b, tables)
        y[lo:hi] = yb
        for t, tree in enumerate(followed):
            leaf[t, lo:hi] = tree.walk(xb, unknown_as)
        for f, col in cols.items():
            col[lo:hi] = np.where(np.isnan(xb[:, f]), -1, xb[:, f]).astype(np.int32)

    with ThreadPoolExecutor(max_workers=threads or datagen_ctr.worker_threads()) as pool:
        list(pool.map(one, range(spec.blocks)))
    return leaf, y, cols


def _subset_node(tree: Tree, i: int, infos: list, p: Params) -> bool:
    """Whether categorical node ``i`` came from the sorted-subset branch: its
    column has more bins than ``max_cat_to_onehot`` (bin 0 counted)."""
    return len(binned_categories(infos, int(tree.split_feature[i]))) + 1 > p.max_cat_to_onehot


def recompute(trees: list, leaf: np.ndarray, y: np.ndarray, params: Params, infos: list,
              cols: dict | None = None, keep=None) -> dict:
    """Leaf counts, leaf values and split gains of the followed trees from the
    reference's own scores, in float64, as ``reference.recompute`` with the
    published ``cat_l2`` on the children of sorted-subset nodes; with ``cols``
    also, per categorical node, the reference's own search on the node's rows
    (``search``: ``(node, best, best one-vs-rest)`` rows) and the law's
    violations.  ``keep`` (a planted fault) is a 0/1 row vector."""
    pavg = min(max(float(y.mean()), 1e-15), 1.0 - 1e-15)
    bias = float(np.log(pavg / (1.0 - pavg)))
    score = np.full(len(y), bias, np.float64)
    l2 = params.lambda_l2
    res = {"bias": bias, "count": [], "out": [], "gain": [], "search": [], "violations": 0}
    for t in range(leaf.shape[0]):
        tree, lt = trees[t], leaf[t]
        prob = _sigmoid(score)
        g, h = prob - y, prob * (1.0 - prob)
        if keep is not None:
            g, h = g * keep, h * keep
        L = tree.num_leaves
        G = np.bincount(lt, weights=g, minlength=L)
        H = np.bincount(lt, weights=h, minlength=L)
        cnt = np.bincount(lt, weights=keep, minlength=L).astype(np.int64)
        res["count"].append(cnt)
        nG, nH = tree.children_sums(G), tree.children_sums(H)
        lG = np.array([tree.side_sum(c, G, nG) for c in tree.left])
        lH = np.array([tree.side_sum(c, H, nH) for c in tree.left])
        rG, rH = nG - lG, nH - lH
        kid_l2 = np.array([l2 + params.cat_l2 if tree.is_cat[i] and
                           _subset_node(tree, i, infos, params) else l2
                           for i in range(L - 1)])
        with np.errstate(invalid="ignore", divide="ignore"):   # a leaf a fault left empty
            out = -G / (H + l2) * params.learning_rate
            gain = lG * lG / (lH + kid_l2) + rG * rG / (rH + kid_l2) - nG * nG / (nH + l2)
        res["gain"].append(gain)
        res["out"].append(out)
        if cols is not None:
            rows, viol = _search_nodes(tree, lt, cnt, g, h, cols, infos, params)
            res["search"].append(rows)
            res["violations"] += viol
        score += out[lt]
    return res


def _search_nodes(tree: Tree, lt, cnt, g, h, cols, infos, p: Params) -> tuple:
    """Per categorical node of ``tree``: the reference's own best gain on the
    node's rows, over all subsets the law allows and one-vs-rest alone; and
    how often the tree breaks the law."""
    rank, span = tree.leaf_ranges()
    order = np.argsort(rank[lt], kind="stable")
    starts = np.concatenate([[0], np.cumsum(np.bincount(rank[lt], minlength=tree.num_leaves))])
    n_cnt = tree.children_sums(cnt)
    rows, viol = [], 0
    for i in np.flatnonzero(tree.is_cat):
        f = int(tree.split_feature[i])
        cats = binned_categories(infos, f)
        left = tree.left_sets[i]
        onehot = len(cats) + 1 <= p.max_cat_to_onehot
        viol += int(len(left) > (1 if onehot else p.max_cat_threshold))
        viol += int(np.setdiff1d(left, cats).size)
        lc = tree.side_sum(tree.left[i], cnt, n_cnt)
        viol += int(lc < p.min_data_in_leaf) + int(n_cnt[i] - lc < p.min_data_in_leaf)
        bin_of = np.zeros(int(max(cats.max(initial=0), cols[f].max(initial=0))) + 2, np.int64)
        bin_of[cats] = np.arange(1, len(cats) + 1)
        at = order[starts[span[i, 0]]:starts[span[i, 1]]]
        b = bin_of[cols[f][at]]                 # -1 (missing) reads the last entry: 0
        nb = len(cats) + 1
        hist = [np.bincount(b, weights=w, minlength=nb) for w in (g[at], h[at], None)]
        best, _ = search(*hist, p)
        best_oh, _ = search(*hist, p, onehot_only=True)
        rows.append((int(i), float(best), float(best_oh)))
    return rows, viol


def cat_search_gap(trees: list, searched: list, stated_gain=None) -> float:
    """Worst relative shortfall, over the categorical nodes of the followed
    trees, of the gain the program states against the reference's own best.
    ``stated_gain(t, node, best one-vs-rest)`` (a planted fault) replaces a
    node's stated gain."""
    worst = 0.0
    for t, rows in enumerate(searched):
        for node, best, best_oh in rows:
            got = trees[t].split_gain[node] if stated_gain is None \
                else stated_gain(t, node, best_oh)
            if best > 0:
                worst = max(worst, (best - got) / best)
    return float(worst)


def train_score_gap(spec, seed, trees: list, scores: dict) -> float:
    """Worst |program's final training score - sum of all its trees' leaf
    values| over the sampled blocks."""
    tables = datagen_ctr.Tables(spec)

    def one(b: int) -> float:
        xb, _ = datagen_ctr.block(spec, seed, b, tables)
        want = predict_raw(trees, xb)
        got = np.asarray(scores[b], np.float64)
        if got.shape != want.shape:        # rows the program never scored
            return float("inf")
        return float(np.max(np.abs(got - want)))

    with ThreadPoolExecutor(max_workers=datagen_ctr.worker_threads()) as pool:
        return max(pool.map(one, sorted(scores)))


def heldout_pred_gap(trees: list, xh: np.ndarray, prob: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(prob, np.float64) - _sigmoid(predict_raw(trees, xh)))))


def followed_numbers(trees: list, ref: dict) -> dict:
    """The numbers of the followed trees: the model text's stated values
    against the reference's (``ref`` from :func:`recompute` with ``cols``)."""
    numbers = compare_followed(stated(trees, ref["bias"]), ref)
    numbers["cat_law_violations"] = float(ref["violations"])
    numbers["cat_search_gap"] = cat_search_gap(trees, ref["search"])
    return numbers


def compare_run(spec, seed, params: Params, model_text: str, scores: dict,
                xh: np.ndarray, prob: np.ndarray, pred_trees: int, fault: str | None = None
                ) -> tuple:
    """Every number a run compares, from the answers the program gave.
    ``fault``: None or one of :data:`FAULTS`, planted on the way (the readings tool
    and the tests; a benchmark run plants none).  Returns (numbers, trees,
    feature_infos, the reference's sums)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    trees, infos = parse_model(model_text)
    walked = [_ALTERED[fault](t) for t in trees] if fault in _ALTERED else trees
    unknown_as = unknown_as_most_frequent(trees, infos) if fault == "unknown_as_top" else None
    leaf, labels, cols = walk_followed(spec, seed, walked, cat_features(trees),
                                       unknown_as=unknown_as)
    ref = recompute(walked, leaf, labels, params, infos, cols)
    numbers = followed_numbers(trees, ref)
    if fault == "onehot_only":
        numbers["cat_search_gap"] = cat_search_gap(
            trees, ref["search"], stated_gain=lambda t, node, best_oh: best_oh)
    numbers["train_score_gap"] = train_score_gap(spec, seed, trees, scores)
    numbers["heldout_pred_gap"] = heldout_pred_gap(trees[:pred_trees], xh, prob)
    return numbers, trees, infos, ref


def cat_split_counts(trees: list) -> tuple:
    """(categorical nodes, internal nodes) over ``trees``."""
    return (int(sum(t.is_cat.sum() for t in trees)),
            int(sum(t.num_leaves - 1 for t in trees)))


# ---- planted faults: what the numbers read where the program is wrong ----

def unknown_as_most_frequent(trees: list, infos: list) -> dict:
    """Fault (a): a missing, folded or unseen category walked as the column's
    most frequent binned category (what a binning that keeps no bin for them
    trains), where the program sends them right."""
    out = {}
    for f in cat_features(trees):
        cats = binned_categories(infos, f)
        known = np.zeros(int(cats.max(initial=0)) + 1, bool)
        known[cats] = True
        out[f] = (known, int(cats[0]))
    return out


def _copy(tree: Tree) -> Tree:
    new = object.__new__(Tree)
    new.__dict__.update(tree.__dict__)
    new._member = None
    return new


def cut_left_sets(tree: Tree, most: int) -> Tree:
    """Fault (c): every left set cut to its first ``most`` categories."""
    new = _copy(tree)
    new.left_sets = [None if s is None else s[:most] for s in tree.left_sets]
    return new


def flip_nan_direction(tree: Tree) -> Tree:
    """Fault (d): the default direction of every numerical node that has a
    missing bin, flipped."""
    new = _copy(tree)
    new.default_left = np.where(~tree.is_cat & tree.missing_nan, ~tree.default_left,
                                tree.default_left)
    return new


# what :func:`compare_run` can plant, and on what.  ``unknown_as_top``: the walk
# (a missing, folded or unseen category stands for the most frequent one);
# ``onehot_only``: the stated gains (each categorical node states the best
# one-vs-rest gain on its rows: a program without the sorted-subset search);
# ``cut_left_sets`` / ``flip_nan``: the trees that are walked.
_ALTERED = {"cut_left_sets": lambda t: cut_left_sets(t, 4), "flip_nan": flip_nan_direction}
FAULTS = ("unknown_as_top", "onehot_only", *_ALTERED)
