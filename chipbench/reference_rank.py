"""The plain reference of a LambdaMART training run (``objective=lambdarank``),
and the comparison that decides ``correct``.

It imports nothing of the program.  From the seed it makes the query-aligned
row blocks again (``chipbench/datagen_rank.py``), walks them through the
program's splits in float64, and recomputes in float64, query by query, the
lambdas and hessians of ``rank_objective.hpp``'s ``LambdarankNDCG``:

* documents sorted by score, descending, stable (``std::stable_sort``);
* the pairs (i, j) of sorted positions with i < min(truncation, L - 1),
  j > i and different labels, the higher label ``high``;
* ``delta_ndcg = (gain[high] - gain[low]) * |1/log2(2+high) - 1/log2(2+low)|
  * inverse max DCG`` (the max DCG of the query's labels at the truncation
  level, ``label_gain`` 2^l - 1 unless stated), divided by ``0.01f +
  |s_high - s_low|`` under ``lambdarank_norm`` where the best and worst
  scores differ;
* ``p = 1 / (1 + exp(sigmoid * (s_high - s_low)))``, ``lambda[high] -=
  sigmoid * delta_ndcg * p``, ``lambda[low] += sigmoid * delta_ndcg * p``,
  both hessians ``+= sigmoid^2 * delta_ndcg * p * (1 - p)``;
* under ``lambdarank_norm`` every lambda and hessian of the query times
  ``log2(1 + S) / S``, S the sum of ``2 * sigmoid * delta_ndcg * p``, where
  S > 0.

The sigmoid is computed exactly (the C++ reads it from a table of 2^20
entries, within about 1e-4 of the exact value); queries of one length are
computed together, each pair block without padding.

The numbers are ``chipbench/reference.py``'s, from the reference's own
lambdas of its own scores (``leaf_count_diff``, ``leaf_value_gap``,
``split_gain_gap``, ``split_gain_median_gap``, ``train_score_gap`` on the
rows of the sampled whole queries, ``heldout_pred_gap`` on the raw scores)
and one of its own:

* ``lambda_gap``  the program's lambdas and hessians, read once after the
                  window from its objective on its final scores, against the
                  float64 lambdas and hessians of the same scores, over the
                  rows of the sampled whole queries: per row |got - ref| over
                  |ref| or the median |ref| of the rows that have one,
                  whichever is larger, the worst row of either.

``heldout_auc_6`` in this cell is the within-query pairwise AUC
(:func:`query_auc`), and NDCG@10 (:func:`ndcg_at`) goes into the notes.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen_rank
from .reference import (FOLLOWED_TREES, compare_followed, judge, parse_model,  # noqa: F401
                        predict_raw, stated)

# the C++ adds 0.01f, a float, to the score distance
NORM_EPS = float(np.float32(0.01))
# elements of one pair block the reference computes at a time
PAIR_BATCH = 1 << 21


class Params:
    """What the reference needs of a configuration's ``params`` group."""

    def __init__(self, params: dict):
        if params.get("objective") != "lambdarank":
            raise ValueError("reference_rank follows objective=lambdarank only")
        self.learning_rate = float(params["learning_rate"])
        self.lambda_l2 = float(params.get("lambda_l2", 0.0))
        self.truncation = int(params.get("lambdarank_truncation_level", 30))
        self.norm = bool(params.get("lambdarank_norm", True))
        self.sigmoid = float(params.get("sigmoid", 1.0))
        gain = params.get("label_gain")
        self.label_gain = np.asarray(gain if gain is not None else
                                     [float((1 << i) - 1) for i in range(31)], np.float64)


def inverse_max_dcg(labels: np.ndarray, k: int, label_gain: np.ndarray) -> float:
    """1 / the DCG of one query's labels sorted descending, at the top k; 0
    where that is 0."""
    top = np.sort(np.asarray(labels, np.int64))[::-1][:k]
    dcg = float((label_gain[top] / np.log2(np.arange(len(top)) + 2.0)).sum())
    return 1.0 / dcg if dcg > 0 else 0.0


def _same_length(s: np.ndarray, y: np.ndarray, inv: np.ndarray, p: Params) -> tuple:
    """Lambdas and hessians of k queries of one length L: (k, L) float64
    scores, labels, (k,) inverse max DCGs.  Returns two (k, L) arrays."""
    k, n = s.shape
    lam = np.zeros((k, n))
    hes = np.zeros((k, n))
    if n < 2:
        return lam, hes
    order = np.argsort(-s, axis=1, kind="stable")
    ss = np.take_along_axis(s, order, 1)
    sl = np.take_along_axis(y, order, 1).astype(np.int64)
    t = min(p.truncation, n - 1)
    i = np.arange(t)[:, None]
    j = np.arange(n)[None, :]
    disc = 1.0 / np.log2(np.arange(n) + 2.0)
    li, lj = sl[:, :t, None], sl[:, None, :]
    pair = (j > i)[None] & (li != lj)
    hi_i = li > lj
    high = np.where(hi_i, i[None], j[None])
    low = np.where(hi_i, j[None], i[None])
    rows = np.arange(k)[:, None, None]
    delta = ss[rows, high] - ss[rows, low]
    dn = (p.label_gain[sl[rows, high]] - p.label_gain[sl[rows, low]]) * \
        np.abs(disc[high] - disc[low]) * inv[:, None, None]
    if p.norm:
        spread = (ss[:, 0] != ss[:, -1])[:, None, None]
        dn = np.where(spread, dn / (NORM_EPS + np.abs(delta)), dn)
    pl = 1.0 / (1.0 + np.exp(p.sigmoid * delta))
    ph = pl * (1.0 - pl)
    pl = np.where(pair, -p.sigmoid * dn * pl, 0.0)
    ph = np.where(pair, p.sigmoid * p.sigmoid * dn * ph, 0.0)
    key_hi = (rows * n + high).ravel()
    key_lo = (rows * n + low).ravel()
    lam_s = (np.bincount(key_hi, pl.ravel(), k * n) -
             np.bincount(key_lo, pl.ravel(), k * n)).reshape(k, n)
    hes_s = (np.bincount(key_hi, ph.ravel(), k * n) +
             np.bincount(key_lo, ph.ravel(), k * n)).reshape(k, n)
    if p.norm:
        total = -2.0 * pl.sum(axis=(1, 2))
        factor = np.where(total > 0, np.log2(1.0 + total) / np.where(total > 0, total, 1.0), 1.0)
        lam_s *= factor[:, None]
        hes_s *= factor[:, None]
    np.put_along_axis(lam, order, lam_s, 1)
    np.put_along_axis(hes, order, hes_s, 1)
    return lam, hes


def lambdas(score: np.ndarray, labels: np.ndarray, boundaries: np.ndarray, p: Params,
            threads: int | None = None) -> tuple:
    """Float64 lambdas and hessians of every row: the queries of
    ``boundaries`` computed length by length."""
    score = np.asarray(score, np.float64)
    labels = np.asarray(labels)
    sizes = np.diff(boundaries)
    lam = np.zeros(len(score))
    hes = np.zeros(len(score))
    jobs = []
    for n in np.unique(sizes):
        qs = np.flatnonzero(sizes == n)
        per = max(1, PAIR_BATCH // max(1, min(p.truncation, n) * n))
        jobs += [(int(n), qs[a:a + per]) for a in range(0, len(qs), per)]

    def one(job) -> None:
        n, qs = job
        rows = boundaries[qs][:, None] + np.arange(n)[None, :]
        inv = np.array([inverse_max_dcg(labels[r], p.truncation, p.label_gain) for r in rows])
        lam[rows], hes[rows] = _same_length(score[rows], labels[rows], inv, p)

    with ThreadPoolExecutor(max_workers=threads or datagen_rank.worker_threads()) as pool:
        list(pool.map(one, jobs))
    return lam, hes


def walk_followed(spec, seed: int, trees: list, threads: int | None = None) -> tuple:
    """Make every training row again, block by block, and walk it through the
    first trees.  Returns (leaf ids int16 (k, rows), labels float64 (rows,))."""
    followed = trees[:FOLLOWED_TREES]
    w = datagen_rank.Weights(spec)
    leaf = np.empty((len(followed), spec.rows), np.int16)
    y = np.empty(spec.rows, np.float64)

    def one(b: int) -> None:
        lo, hi = spec.block_range(b)
        xb, yb = datagen_rank.block(spec, seed, b, w)
        y[lo:hi] = yb
        for t, tree in enumerate(followed):
            leaf[t, lo:hi] = tree.walk(xb)

    with ThreadPoolExecutor(max_workers=threads or datagen_rank.worker_threads()) as pool:
        list(pool.map(one, range(spec.blocks)))
    return leaf, y


def recompute(spec, trees: list, leaf: np.ndarray, y: np.ndarray, p: Params) -> dict:
    """Leaf counts, leaf values and split gains of the followed trees from the
    reference's own lambdas of its own scores (from 0: lambdarank boosts from
    no average), in float64."""
    score = np.zeros(len(y))
    res = {"count": [], "out": [], "gain": []}
    for t in range(leaf.shape[0]):
        tree, lt = trees[t], leaf[t]
        g, h = lambdas(score, y, spec.boundaries, p)
        L = tree.num_leaves
        G = np.bincount(lt, weights=g, minlength=L)
        H = np.bincount(lt, weights=h, minlength=L)
        res["count"].append(np.bincount(lt, minlength=L).astype(np.int64))
        nG, nH = tree.children_sums(G), tree.children_sums(H)
        lG = np.array([tree.side_sum(c, G, nG) for c in tree.left])
        lH = np.array([tree.side_sum(c, H, nH) for c in tree.left])
        rG, rH = nG - lG, nH - lH
        l2 = p.lambda_l2
        with np.errstate(invalid="ignore", divide="ignore"):
            out = -G / (H + l2) * p.learning_rate
            gain = lG * lG / (lH + l2) + rG * rG / (rH + l2) - nG * nG / (nH + l2)
        res["out"].append(out)
        res["gain"].append(gain)
        score += out[lt]
    return res


def pairs_visited(lengths: np.ndarray, truncation: int) -> int:
    """The pairs the reference's loop visits, labels aside: over the queries,
    sum over i < min(truncation, L) of (L - 1 - i)."""
    L = np.asarray(lengths, np.int64)
    t = np.minimum(truncation, L)
    return int((t * (L - 1) - t * (t - 1) // 2).sum())


def sample_queries(spec, seed: int, k: int, truncation: int) -> np.ndarray:
    """``k`` whole training queries drawn from the seed, sorted: always the
    longest and the shortest, one of exactly ``truncation`` documents, one
    shorter and one of more than 1,000, where there are such."""
    L = spec.lengths
    rng = np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(1_000_006,)))
    want = [int(np.argmax(L)), int(np.argmin(L))]
    for cond in (L == truncation, (L < truncation) & (L > L.min()), L > 1000):
        cand = np.flatnonzero(cond)
        if len(cand):
            want.append(int(rng.choice(cand)))
    rest = np.setdiff1d(np.arange(spec.queries), want)
    more = rng.choice(rest, size=max(0, min(k - len(set(want)), len(rest))), replace=False)
    return np.unique(np.r_[want, more]).astype(np.int64)


def query_rows(spec, queries: np.ndarray) -> np.ndarray:
    """The rows of ``queries``, in row order."""
    return np.concatenate([np.arange(spec.boundaries[q], spec.boundaries[q + 1])
                           for q in np.sort(queries)])


def sampled_rows(spec, seed: int, queries: np.ndarray) -> tuple:
    """The sampled queries' rows made again from their blocks: (float32
    (n, F), float32 (n,))."""
    w = datagen_rank.Weights(spec)
    xs, ys = [], []
    for b in sorted({spec.block_of_query(int(q)) for q in queries}):
        xb, yb = datagen_rank.block(spec, seed, b, w)
        lo = spec.block_range(b)[0]
        mine = [q for q in queries if spec.block_of_query(int(q)) == b]
        r = query_rows(spec, np.asarray(mine)) - lo
        xs.append(xb[r])
        ys.append(yb[r])
    return np.concatenate(xs), np.concatenate(ys)


def lambda_gap(got_lam, got_hess, ref_lam, ref_hess) -> float:
    """The worst row's |got - ref| over |ref| or the median |ref| of the rows
    that have one, whichever is larger, of the lambdas or the hessians."""
    worst = 0.0
    for got, ref in ((got_lam, ref_lam), (got_hess, ref_hess)):
        got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
        nz = np.abs(ref[ref != 0])
        floor = float(np.median(nz)) if len(nz) else 1.0
        worst = max(worst, float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), floor))))
    return worst


class Sample:
    """What the program gave on the sampled whole queries: their ids, and on
    their rows (in row order) its final training scores, lambdas and hessians."""

    def __init__(self, queries, scores, lam, hess):
        self.queries = np.asarray(queries, np.int64)
        self.scores = np.asarray(scores, np.float64)
        self.lam = np.asarray(lam, np.float64)
        self.hess = np.asarray(hess, np.float64)


def compare_sample(spec, seed: int, p: Params, trees: list, sample: Sample) -> dict:
    """``train_score_gap`` and ``lambda_gap`` on the sampled queries."""
    x, y = sampled_rows(spec, seed, sample.queries)
    want = predict_raw(trees, x)
    if sample.scores.shape != want.shape:              # rows the program never scored
        return {"lambda_gap": float("inf"), "train_score_gap": float("inf")}
    sizes = np.diff(spec.boundaries)[np.sort(sample.queries)]
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    ref_lam, ref_hess = lambdas(sample.scores, y, bounds, p)
    return {"lambda_gap": lambda_gap(sample.lam, sample.hess, ref_lam, ref_hess),
            "train_score_gap": float(np.max(np.abs(sample.scores - want)))}


def heldout_pred_gap(trees: list, xh: np.ndarray, pred: np.ndarray) -> float:
    """Worst |program's held-out raw score - the float64 walk of the same trees|."""
    return float(np.max(np.abs(np.asarray(pred, np.float64) - predict_raw(trees, xh))))


def compare_run(spec, seed, p: Params, model_text: str, sample: Sample,
                xh: np.ndarray, pred: np.ndarray, pred_trees: int) -> tuple:
    """Every number a run compares, from the answers the program gave: its
    model text, what it gave on the sampled queries, and its held-out raw
    scores with the first ``pred_trees`` trees.  Returns (numbers, trees)."""
    trees = parse_model(model_text)
    leaf, labels = walk_followed(spec, seed, trees)
    ref = recompute(spec, trees, leaf, labels, p)
    numbers = compare_followed(stated(trees, 0.0), ref)
    numbers.update(compare_sample(spec, seed, p, trees, sample))
    numbers["heldout_pred_gap"] = heldout_pred_gap(trees[:pred_trees], xh, pred)
    return numbers, trees


# ---- what the held-out scores are worth -------------------------------------

def query_auc(y: np.ndarray, s: np.ndarray, lengths: np.ndarray) -> float:
    """Within-query pairwise AUC: per query, the share of its document pairs
    with different labels that the scores order as the labels do (ties count
    half), averaged over the queries that have such a pair."""
    at, out = 0, []
    for n in np.asarray(lengths, np.int64):
        yq, sq = y[at:at + n], s[at:at + n]
        at += n
        higher = yq[:, None] > yq[None, :]
        pairs = int(higher.sum())
        if pairs == 0:
            continue
        ds = sq[:, None] - sq[None, :]
        good = (higher & (ds > 0)).sum() + 0.5 * (higher & (ds == 0)).sum()
        out.append(good / pairs)
    if not out:
        raise ValueError("query_auc needs a query with two different labels")
    return float(np.mean(out))


def ndcg_at(y: np.ndarray, s: np.ndarray, lengths: np.ndarray, k: int = 10,
            label_gain: np.ndarray | None = None) -> float:
    """Mean NDCG@k over the queries (a query whose ideal DCG is 0 counts 1,
    as the reference's metric counts it)."""
    gain = Params({"objective": "lambdarank", "learning_rate": 0}).label_gain \
        if label_gain is None else label_gain
    at, out = 0, []
    for n in np.asarray(lengths, np.int64):
        yq, sq = y[at:at + n].astype(np.int64), s[at:at + n]
        at += n
        disc = 1.0 / np.log2(np.arange(min(k, n)) + 2.0)
        ideal = float((gain[np.sort(yq)[::-1][:k]] * disc).sum())
        got = float((gain[yq[np.argsort(-sq, kind="stable")][:k]] * disc).sum())
        out.append(got / ideal if ideal > 0 else 1.0)
    return float(np.mean(out))
