"""The plain reference of a boosted-tree training run on SPARSE one-hot rows,
and the comparison that decides ``correct``.

It imports nothing of the program.  From the seed it makes the CSR row blocks
again (``chipbench/datagen_sparse.py``) and walks them in float64 IN
ORIGINAL-FEATURE SPACE: a node compares one raw column's value with its
threshold.  It has no notion of a bundle.  What the program does with exclusive
columns inside (Exclusive Feature Bundling) shows only in what it STATES of it:
``conflicts``, the entries (row, column) of the raw data it trained on as
zero because another column of the same bundle was set in that row, at most
1e-4 of the rows a bundle.  The reference takes the list as the program's
statement of the data it trained on: it checks that every listed entry is a
stored value of the raw rows and holds their number to the stated count, sets
those entries to zero, and from there holds every row to the leaf the trees
give it: no other departure is allowed for.  Held-out rows are never
corrected: prediction reads raw values and needs no bundle.

The numbers are ``chipbench/reference.py``'s (``leaf_count_diff``,
``leaf_value_gap``, ``split_gain_gap``, ``split_gain_median_gap``,
``train_score_gap``, ``heldout_pred_gap``) and two of its own:

* ``conflict_statement_errors``  listed entries that are no stored value of the
                       raw rows, plus the distance between the listed rows and
                       the stated ``conflict_rows``: exact
* ``indicator_search_gap``  at every node of the followed trees, the best gain
                       that any indicator column offers on the node's rows
                       (each has exactly one split, 0 | 1; per-(leaf, column)
                       sums by ``bincount`` over the stored values;
                       ``min_sum_hessian_in_leaf`` applied) over the gain of
                       the split the program committed there, as far as it
                       is larger: a wrong expansion of the bundle histograms,
                       a default bin restored wrongly, a member dropped from
                       the scan all leave a better indicator split unseen.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen_sparse
from .reference import (FOLLOWED_TREES, _sigmoid, compare_followed, parse_model, recompute,
                        stated)
from .reference import Params as _Params

# an indicator split one of whose sides holds a hessian sum this close above
# min_sum_hessian_in_leaf is left out of the search: the program's int8 sums
# can put it below
HESSIAN_MARGIN = 0.05


class Params(_Params):
    """What the reference needs of a configuration's ``params`` group."""

    def __init__(self, params: dict):
        super().__init__(params)
        self.min_sum_hessian = float(params["min_sum_hessian_in_leaf"])
        if int(params.get("min_data_in_leaf", 20)) != 0:
            raise ValueError("reference_sparse searches under min_data_in_leaf=0 only")


class Conflicts:
    """The program's statement of the entries it trained on as zero."""

    def __init__(self, rows, columns, stated_rows: int):
        order = np.argsort(np.asarray(rows, np.int64), kind="mergesort")
        self.rows = np.asarray(rows, np.int64)[order]
        self.columns = np.asarray(columns, np.int64)[order]
        self.stated_rows = int(stated_rows)

    def of_block(self, lo: int, hi: int) -> tuple:
        a, b = np.searchsorted(self.rows, [lo, hi])
        return self.rows[a:b] - lo, self.columns[a:b]


NO_CONFLICTS = Conflicts([], [], 0)


def zero_entries(csr, rows: np.ndarray, columns: np.ndarray) -> int:
    """Set the stored values at (rows[i], columns[i]) of ``csr`` to zero, in
    place; returns how many of them are no stored non-zero value."""
    if len(rows) == 0:
        return 0
    f = csr.shape[1]
    keys = np.repeat(np.arange(csr.shape[0], dtype=np.int64), np.diff(csr.indptr)) * f + \
        csr.indices
    want = rows * f + columns
    at = np.minimum(np.searchsorted(keys, want), len(keys) - 1)
    found = (keys[at] == want) & (csr.data[at] != 0)
    csr.data[at[found]] = 0
    return int(np.count_nonzero(~found))


def walk(tree, csc) -> np.ndarray:
    """Leaf index of every row of the CSC block: at a node, left iff the raw
    column's value (zero where nothing is stored) <= threshold, in float64."""
    n = csc.shape[0]
    if tree.num_leaves == 1:
        return np.zeros(n, np.int32)
    leaf = np.empty(n, np.int32)
    at = {0: np.arange(n)}
    buf = np.zeros(n, np.float64)
    for i in range(tree.num_leaves - 1):     # children carry larger indices
        rows = at.pop(i)
        lo, hi = csc.indptr[tree.split_feature[i]], csc.indptr[tree.split_feature[i] + 1]
        buf[csc.indices[lo:hi]] = csc.data[lo:hi]
        left = buf[rows] <= tree.threshold[i]
        buf[csc.indices[lo:hi]] = 0.0
        for child, part in ((tree.left[i], rows[left]), (tree.right[i], rows[~left])):
            if child >= 0:
                at[child] = part
            else:
                leaf[part] = ~child
    return leaf


def predict_raw(trees: list, csr) -> np.ndarray:
    """Float64 raw score of sparse rows: the sum of each tree's leaf value."""
    out = np.zeros(csr.shape[0], np.float64)
    csc = csr.tocsc()
    for t in trees:
        out += t.leaf_value[walk(t, csc)]
    return out


def swap_offsets(spec, tree):
    """Fault: every split on an indicator column walked on its neighbour in
    the same coded column (two members' places in a bundle swapped)."""
    new = object.__new__(type(tree))
    new.__dict__.update(tree.__dict__)
    f = tree.split_feature.copy()
    for name in spec.coded_names:
        o, k = spec.offset[name], spec.levels[name]
        inside = (f >= o) & (f < o + k - k % 2)      # an odd last level keeps its place
        f = np.where(inside, o + ((f - o) ^ 1), f)
    new.split_feature = f
    return new


def _as_trained(spec, xb, conflicts: Conflicts, lo: int, hi: int, by_loser: bool) -> int:
    """The block as the program states it trained on it, in place: the stated
    conflict entries zeroed (``by_loser``, the planted fault: kept, and the
    row's other indicators zeroed).  Returns the stated entries that are no
    stored value of the block."""
    rows, columns = conflicts.of_block(lo, hi)
    if by_loser:
        routed_by_loser(spec, xb, rows, columns)
        return 0
    return zero_entries(xb, rows, columns)


def walk_followed(spec, seed: int, trees: list, conflicts: Conflicts,
                  threads: int | None = None, by_loser: bool = False) -> tuple:
    """Make every training row again, block by block, zero the stated
    conflict entries and walk the rows through the first trees.  Returns (leaf
    ids int16 (k, rows), labels float64 (rows,), the set indicator column of
    every coded column int16 (rows, n_coded), -1 where zeroed, and how many
    stated entries are no stored value)."""
    followed = trees[:FOLLOWED_TREES]
    t = datagen_sparse.Tables(spec)
    leaf = np.empty((len(followed), spec.rows), np.int16)
    y = np.empty(spec.rows, np.float64)
    cols = np.empty((spec.rows, spec.n_coded), np.int16)
    missing = []

    def one(b: int) -> None:
        lo, hi = spec.block_range(b)
        xb, yb = datagen_sparse.block(spec, seed, b, t)
        missing.append(_as_trained(spec, xb, conflicts, lo, hi, by_loser))
        y[lo:hi] = yb
        ind = spec.is_indicator[xb.indices]
        cols[lo:hi] = np.where(xb.data[ind] != 0, xb.indices[ind], -1).reshape(
            hi - lo, spec.n_coded)
        csc = xb.tocsc()
        for k, tree in enumerate(followed):
            leaf[k, lo:hi] = walk(tree, csc)

    with ThreadPoolExecutor(max_workers=threads or datagen_sparse.worker_threads()) as pool:
        list(pool.map(one, range(spec.blocks)))
    return leaf, y, cols, int(sum(missing))


def _node_sums(tree, leaf_sums: np.ndarray) -> np.ndarray:
    """Per internal node the sum of ``leaf_sums`` (L, ...) over its leaves."""
    out = np.zeros((tree.num_leaves - 1,) + leaf_sums.shape[1:], leaf_sums.dtype)
    for i in range(tree.num_leaves - 2, -1, -1):
        for c in (tree.left[i], tree.right[i]):
            out[i] += out[c] if c >= 0 else leaf_sums[~c]
    return out


def indicator_sums(spec, trees: list, leaf: np.ndarray, y: np.ndarray, cols: np.ndarray,
                   ref: dict) -> list:
    """Per followed tree ``(G1, H1)``: per (internal node, column) the gradient
    and hessian sums of the node's rows whose indicator is set, from the
    reference's own scores."""
    f1 = spec.features + 1                 # the last column takes the zeroed entries
    out = []
    score = np.full(len(y), ref["bias"], np.float64)
    for k, tree in enumerate(trees[:leaf.shape[0]]):
        p = _sigmoid(score)
        g, h = p - y, p * (1.0 - p)
        L = tree.num_leaves
        base = leaf[k].astype(np.int64) * f1
        G1, H1 = np.zeros(L * f1), np.zeros(L * f1)
        for s in range(cols.shape[1]):
            key = base + np.where(cols[:, s] < 0, spec.features, cols[:, s])
            G1 += np.bincount(key, weights=g, minlength=L * f1)
            H1 += np.bincount(key, weights=h, minlength=L * f1)
        out.append((_node_sums(tree, G1.reshape(L, f1))[:, :-1],
                    _node_sums(tree, H1.reshape(L, f1))[:, :-1]))
        score += ref["out"][k][leaf[k]]
    return out


def _gain(g, h, l2):
    return g * g / (h + l2)


def indicator_search(spec, trees: list, sums: list, ref: dict, params: Params,
                     allowed: np.ndarray | None = None) -> list:
    """Per followed tree and node, the best gain an indicator column offers
    (NaN where none is valid); ``allowed``: the columns searched."""
    l2, need = params.lambda_l2, params.min_sum_hessian * (1.0 + HESSIAN_MARGIN)
    cols = spec.is_indicator if allowed is None else spec.is_indicator & allowed
    out = []
    for k, (G1, H1) in enumerate(sums):
        tree = trees[k]
        nG = tree.children_sums(ref["G"][k])[:, None]
        nH = tree.children_sums(ref["H"][k])[:, None]
        with np.errstate(invalid="ignore", divide="ignore"):
            gain = _gain(G1, H1, l2) + _gain(nG - G1, nH - H1, l2) - _gain(nG, nH, l2)
        ok = (H1 >= need) & (nH - H1 >= need) & cols[None, :]
        best = np.where(ok, gain, -np.inf).max(axis=1)
        out.append(np.where(np.isfinite(best), best, np.nan))
    return out


def indicator_search_gap(ref: dict, searched: list, stated_gain: list | None = None) -> float:
    """The worst node's (best indicator gain - committed gain), where positive,
    against the committed gain or the tree's median one, whichever is larger.
    ``stated_gain`` (a planted fault): the gains committed instead of the
    reference's own."""
    worst = 0.0
    for k, best in enumerate(searched):
        got = ref["gain"][k] if stated_gain is None else stated_gain[k]
        scale = np.maximum(np.abs(ref["gain"][k]), np.median(np.abs(ref["gain"][k])))
        over = np.where(np.isnan(best), 0.0, np.maximum(best - got, 0.0) / scale)
        worst = max(worst, float(over.max(initial=0.0)))
    return worst


def train_score_gap(spec, seed, trees: list, scores: dict, conflicts: Conflicts,
                    by_loser: bool = False) -> float:
    """Worst |program's final training score - sum of all its trees' leaf
    values| over the sampled blocks, the stated conflict entries zeroed."""
    t = datagen_sparse.Tables(spec)

    def one(b: int) -> float:
        xb, _ = datagen_sparse.block(spec, seed, b, t)
        _as_trained(spec, xb, conflicts, *spec.block_range(b), by_loser)
        want = predict_raw(trees, xb)
        got = np.asarray(scores[b], np.float64)
        if got.shape != want.shape:        # rows the program never scored
            return float("inf")
        return float(np.max(np.abs(got - want)))

    with ThreadPoolExecutor(max_workers=datagen_sparse.worker_threads()) as pool:
        return max(pool.map(one, sorted(scores)))


def heldout_pred_gap(trees: list, xh, prob: np.ndarray) -> float:
    """Worst |program's predicted probability - sigmoid of the float64 walk|
    over the held-out rows (raw values: nothing is zeroed)."""
    step = datagen_sparse.BLOCK_ROWS
    parts = [predict_raw(trees, xh[lo:lo + step]) for lo in range(0, xh.shape[0], step)]
    return float(np.max(np.abs(np.asarray(prob, np.float64) - _sigmoid(np.concatenate(parts)))))


def _unrestored_gains(spec, trees: list, sums: list, ref: dict, params: Params) -> list:
    """Fault: what a program that leaves a bundle member's default bin empty
    states as the gain of its indicator splits (the left side holds nothing)."""
    out = []
    for k, (G1, H1) in enumerate(sums):
        tree, gain = trees[k], ref["gain"][k].copy()
        nG, nH = tree.children_sums(ref["G"][k]), tree.children_sums(ref["H"][k])
        for i in np.flatnonzero(spec.is_indicator[tree.split_feature]):
            c = tree.split_feature[i]
            gain[i] = _gain(G1[i, c], H1[i, c], params.lambda_l2) - \
                _gain(nG[i], nH[i], params.lambda_l2)
        out.append(gain)
    return out


# The reference knows no bundle: runs of this many consecutive indicator columns
# stand in for one where a fault needs "every second bundle"; short enough that
# every coded column of a deployment straddles a skipped run (in runs of 254, a
# bundle's room, the makes and all of Cat1-NVCat of the Allstate coding fall into
# runs that are read, and the fault shows in no followed tree)
BUNDLE_ROOM = 4


def _second_bundles(spec) -> np.ndarray:
    """The columns a scan that skips every second bundle still reads: the
    indicator columns in even runs of :data:`BUNDLE_ROOM`, and the rest."""
    rank = np.cumsum(spec.is_indicator) - 1
    return ~spec.is_indicator | ((rank // BUNDLE_ROOM) % 2 == 0)


def routed_by_loser(spec, csr, rows: np.ndarray, columns: np.ndarray) -> None:
    """Fault: a bundle's conflict rows routed by the losing member.  In a
    listed row the listed entry is KEPT and the row's other indicator entries
    are set to zero, in place (the reference does not know which of them
    shared the bundle: the winner is among them)."""
    if len(rows) == 0:
        return
    f = csr.shape[1]
    row_of = np.repeat(np.arange(csr.shape[0], dtype=np.int64), np.diff(csr.indptr))
    listed = np.zeros(csr.shape[0], bool)
    listed[rows] = True
    kept = np.isin(row_of * f + csr.indices, rows * f + columns)
    csr.data[listed[row_of] & spec.is_indicator[csr.indices] & ~kept] = 0


FAULTS = ("default_not_restored", "offsets_swapped", "conflicts_by_loser",
          "skip_second_bundle")


def compare_run(spec, seed, params: Params, model_text: str, scores: dict, xh,
                prob: np.ndarray, pred_trees: int, conflicts: Conflicts = NO_CONFLICTS,
                fault: str | None = None) -> tuple:
    """Every number a run compares, from the answers the program gave: its
    model text, its statement of the conflict entries, its final training
    scores on the sampled blocks, and its predictions on the held-out rows with
    the first ``pred_trees`` trees.  ``fault``: None or one of :data:`FAULTS`,
    planted on the way (the readings tool and the tests; a benchmark run plants
    none).  Returns (numbers, trees, the reference's sums)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    trees = parse_model(model_text)
    walked = [swap_offsets(spec, t) for t in trees] if fault == "offsets_swapped" else trees
    by_loser = fault == "conflicts_by_loser"
    leaf, labels, cols, missing = walk_followed(spec, seed, walked, conflicts,
                                                by_loser=by_loser)
    ref = recompute(walked, leaf, labels, params)
    got = stated(trees, ref["bias"])
    sums = indicator_sums(spec, walked, leaf, labels, cols, ref)
    if fault == "default_not_restored":
        got["gain"] = _unrestored_gains(spec, trees, sums, ref, params)
    numbers = compare_followed(got, ref)
    numbers["conflict_statement_errors"] = float(
        missing + abs(len(np.unique(conflicts.rows)) - conflicts.stated_rows))
    searched = indicator_search(spec, walked, sums, ref, params)
    stated_gain = None
    if fault == "skip_second_bundle":
        seen = _second_bundles(spec)
        skipped = indicator_search(spec, walked, sums, ref, params, allowed=seen)
        stated_gain = [np.where(seen[t.split_feature], ref["gain"][k],
                                np.minimum(ref["gain"][k], np.nan_to_num(skipped[k], nan=0.0)))
                       for k, t in enumerate(trees[:len(searched)])]
    numbers["indicator_search_gap"] = indicator_search_gap(ref, searched, stated_gain)
    numbers["train_score_gap"] = train_score_gap(spec, seed, trees, scores, conflicts,
                                                 by_loser)
    numbers["heldout_pred_gap"] = heldout_pred_gap(trees[:pred_trees], xh, prob)
    return numbers, trees, ref


def indicator_split_counts(spec, trees: list) -> list:
    """Per tree, the splits on indicator columns."""
    return [int(spec.is_indicator[t.split_feature].sum()) for t in trees]
