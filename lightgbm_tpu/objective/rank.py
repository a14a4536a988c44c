"""Learning-to-rank objectives (reference src/objective/rank_objective.hpp:
``RankingObjective`` base parallelizing per query at :25-67, LambdarankNDCG
pairwise lambdas at :140-227, RankXENDCG at :284-352).

The reference loops documents per query with OpenMP.  Here lambdarank lays
its queries out in LENGTH BUCKETS (:class:`QueryLayout`): a query of L
documents takes a column of the bucket whose width is the least power of two
>= max(L, 8), the widest bucket no wider than the longest query.  A bucket
holds its documents along the first axis and its queries along the second,
so on the chip the queries fill the lanes.  The reference's pair loop pairs
only the top ``lambdarank_truncation_level`` sorted positions with the rest
(``i < truncation_level``), so a bucket of width M evaluates a (T, M) pair
block a query, T = min(truncation, M), not (M, M): its sums over the
positions give the lambdas of the top T, its sums over the top T those of
every position.  Each row of the training set has exactly one slot in the
layout; the rows reach their slots, and the lambdas return to row order, by
sorts on static keys: a TPU gather moves one element at a time (on a v5e
the three gathers, 10.3 M slots in and 7.3 M lambdas and hessians out, took
200 ms an iteration, the two sorts that replace them about 80 ms).  The sigmoid lookup table
(rank_objective.hpp:249) is unnecessary — the VPU computes exact sigmoids
faster than a gather.

``rank_xendcg`` keeps one (Q, M) padding of every query to the longest
(:func:`pad_queries`): its per-query work is a softmax, linear in M."""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .base import EPS, ObjectiveFunction

KMIN_SCORE = -1e30
MIN_BUCKET_WIDTH = 8
# elements of one chunk's (T, M, chunk) pair tensor (~64 MB a float32 plane)
PAIR_CHUNK_ELEMENTS = 16 << 20
LANES = 128


def pad_queries(query_boundaries: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Build (Q, M) flat-index + validity tensors from query boundaries,
    M the longest query rounded up to a multiple of 8."""
    sizes = np.diff(query_boundaries)
    m = max(1, int(sizes.max()) if len(sizes) else 1)
    m = int(np.ceil(m / 8) * 8)
    pos = np.arange(m)
    valid = pos[None, :] < sizes[:, None]
    idx = np.where(valid, query_boundaries[:-1, None] + pos[None, :], 0)
    return idx.astype(np.int32), valid


def bucket_width(sizes: np.ndarray) -> np.ndarray:
    """Per query, the width of its bucket: the least power of two >= max(L,
    8), capped at the longest query rounded up to a multiple of 8."""
    sizes = np.asarray(sizes, np.int64)
    top = int(np.ceil(max(int(sizes.max()), 1) / 8) * 8)
    pow2 = np.left_shift(1, np.ceil(np.log2(np.maximum(sizes, 1))).astype(np.int64))
    return np.minimum(np.maximum(pow2, MIN_BUCKET_WIDTH), top)


def pairs_counted(sizes: np.ndarray, truncation: int) -> int:
    """The pairs (i, j), i < j, i < truncation in sorted order, over all
    queries: what the reference's loop visits, labels aside."""
    L = np.asarray(sizes, np.int64)
    t = np.minimum(truncation, L)
    return int((t * (L - 1) - t * (t - 1) // 2).sum())


class QueryLayout:
    """Queries in length buckets, documents along the first axis and queries
    along the second (host arrays, built without a loop over queries): on
    the chip the queries fill the lanes.

    Per bucket, in ascending width: ``widths[b]``, ``query_ids[b]`` (Q_b,),
    ``idx[b]`` (M_b, Q_b) int32 rows of the training set (0 where padded),
    ``valid[b]`` (M_b, Q_b) bool.  Over the buckets' (M_b, Q_b) slots laid
    end to end (``row_slots`` of them): ``slot`` (N,) int32, each row's
    slot; ``pad_slots``, the slots no row takes; ``slot_row`` (row_slots,)
    int32, each slot's row, the padding slots numbered on from N."""

    def __init__(self, query_boundaries: np.ndarray, num_data: int):
        qb = np.asarray(query_boundaries, np.int64)
        sizes = np.diff(qb)
        if len(sizes) == 0 or sizes.min() < 0:
            raise ValueError("query sizes must be non-negative, and one query at least")
        if qb[0] != 0 or qb[-1] != num_data:
            raise ValueError(f"queries cover rows {qb[0]}..{qb[-1]}; the data has "
                             f"{num_data} rows")
        self.sizes = sizes
        width = bucket_width(sizes)
        self.widths: List[int] = [int(w) for w in np.unique(width)]
        self.query_ids: List[np.ndarray] = []
        self.idx: List[np.ndarray] = []
        self.valid: List[np.ndarray] = []
        self.slot = np.empty(num_data, np.int32)
        base = 0
        for w in self.widths:
            q = np.flatnonzero(width == w)
            pos = np.arange(w)[:, None]
            valid = pos < sizes[None, q]
            rows = qb[None, q] + pos
            self.query_ids.append(q)
            self.idx.append(np.where(valid, rows, 0).astype(np.int32))
            self.valid.append(valid)
            self.slot[rows[valid]] = base + np.flatnonzero(valid.ravel())
            base += valid.size
        self.row_slots = base
        # sorted by slot_row, the slots come back in row order, padding last
        self.slot_row = np.empty(base, np.int32)
        self.slot_row[self.slot] = np.arange(num_data, dtype=np.int32)
        pad = np.ones(base, bool)
        pad[self.slot] = False
        self.pad_slots = np.flatnonzero(pad).astype(np.int32)
        self.slot_row[self.pad_slots] = num_data + np.arange(len(self.pad_slots))

    def pair_width(self, b: int, truncation: int) -> int:
        return min(int(truncation), self.widths[b])

    def record(self, truncation: int) -> Dict[str, object]:
        """The layout as a ``TrainRecord`` states it."""
        pair_slots = sum(len(q) * self.pair_width(b, truncation) * w
                         for b, (w, q) in enumerate(zip(self.widths, self.query_ids)))
        return {"queries": int(len(self.sizes)),
                "longest_query": int(self.sizes.max()),
                "truncation_level": int(truncation),
                "buckets": [[int(w), int(len(q))]
                            for w, q in zip(self.widths, self.query_ids)],
                "row_slots": int(self.row_slots),
                "pair_slots": int(pair_slots),
                "pairs_counted": pairs_counted(self.sizes, truncation)}


def max_dcg_at_k(labels: np.ndarray, valid: np.ndarray, k: int,
                 label_gain: np.ndarray) -> np.ndarray:
    """Per column of an (M, Q) bucket, the DCG of its labels in descending
    order at the top ``k`` (dcg_calculator.cpp CalMaxDCGAtK), float64."""
    lab = np.where(valid, labels, -1.0)
    top = -np.sort(-lab, axis=0)[:k]
    disc = 1.0 / np.log2(np.arange(top.shape[0]) + 2.0)
    gains = np.where(top >= 0, label_gain[np.maximum(top, 0).astype(np.int64)], 0.0)
    return (gains * disc[:, None]).sum(axis=0)


class RankingObjective(ObjectiveFunction):
    need_group = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            raise ValueError(f"objective {self.name} requires query/group data")
        self.query_boundaries = metadata.query_boundaries
        self.num_queries = len(self.query_boundaries) - 1


class LambdarankNDCG(RankingObjective):
    name = "lambdarank"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        self.norm = bool(config.lambdarank_norm)
        self.truncation_level = int(config.lambdarank_truncation_level)
        self.label_gain = np.asarray(config.label_gain, dtype=np.float64)

    def check_label(self, label):
        if (label < 0).any():
            raise ValueError("ranking labels must be non-negative integers")
        if int(label.max()) >= len(self.label_gain):
            raise ValueError("label exceeds label_gain size")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        layout = QueryLayout(self.query_boundaries, num_data)
        self.layout = layout
        lab = np.asarray(metadata.label, np.float32)
        buckets, base = [], 0
        for idx, valid in zip(layout.idx, layout.valid):
            lb = np.where(valid, lab[idx], 0.0).astype(np.float32)
            # inverse max DCG per query at the truncation level
            # (rank_objective.hpp:121-135 via dcg_calculator.cpp CalMaxDCGAtK)
            mdcg = max_dcg_at_k(lb, valid, self.truncation_level, self.label_gain)
            inv = np.where(mdcg > 0, 1.0 / np.where(mdcg > 0, mdcg, 1.0), 0.0)
            gain = self.label_gain[lb.astype(np.int64)]
            rows = layout.slot_row[base:base + valid.size].reshape(valid.shape)
            base += valid.size
            buckets.append(tuple(jnp.asarray(a) for a in (
                rows, valid.sum(axis=0).astype(np.int32), lb, gain.astype(np.float32),
                inv.astype(np.float32))))
        # the discount of every sorted position, rounded once from float64
        # (DCGCalculator's table): the chip's log2 is a few ulp off, and the
        # paired discount of neighbouring positions magnifies that
        disc = 1.0 / np.log2(np.arange(max(layout.widths)) + 2.0)
        # sorted by it, the rows followed by padding land in their slots
        fwd = np.concatenate([layout.slot, layout.pad_slots]).astype(np.int32)
        self._bucket_args = (jnp.asarray(fwd), tuple(buckets), jnp.asarray(disc, jnp.float32))
        self._grad_fn = self._build_grad_fn()

    def record(self) -> Dict[str, object]:
        return {"name": self.name, **self.layout.record(self.truncation_level)}

    def _build_grad_fn(self):
        sig = self.sigmoid
        norm = self.norm
        layout = self.layout
        tops = [layout.pair_width(b, self.truncation_level)
                for b in range(len(layout.widths))]

        def pair_block(ss, sl, sg, n_valid, inv_max_dcg, disc, t):
            """Lambdas and hessians of a chunk of queries sorted along axis 0,
            (M, c): the pairs (i < t, j > i) of the reference's loop, in a
            (t, M, c) block whose two sums give every position's share."""
            m = ss.shape[0]
            pos = jnp.arange(m)
            sv = pos[:, None] < n_valid[None, :]
            best = ss[0]
            worst = jnp.take_along_axis(ss, jnp.maximum(n_valid - 1, 0)[None, :], 0)[0]
            d = disc[:m, None]
            # i: the top t sorted positions (axis 0); j: every position (axis 1)
            s_i, l_i, g_i, v_i, d_i = (a[:t, None, :] for a in (ss, sl, sg, sv, d))
            s_j, l_j, g_j, v_j, d_j = (a[None, :, :] for a in (ss, sl, sg, sv, d))
            pair = (pos[:t, None] < pos[None, :])[:, :, None] & v_i & v_j & (l_i != l_j)
            hi_is_i = l_i > l_j
            delta_score = jnp.where(hi_is_i, s_i - s_j, s_j - s_i)
            delta_ndcg = jnp.abs(g_i - g_j) * jnp.abs(d_i - d_j) * inv_max_dcg[None, None, :]
            if norm:
                delta_ndcg = jnp.where((best != worst)[None, None, :],
                                       delta_ndcg / (0.01 + jnp.abs(delta_score)),
                                       delta_ndcg)
            p = 1.0 / (1.0 + jnp.exp(sig * delta_score))
            p_lambda = jnp.where(pair, -sig * delta_ndcg * p, 0.0)
            p_hess = jnp.where(pair, sig * sig * delta_ndcg * p * (1.0 - p), 0.0)
            # lambdas[high] += p_lambda, lambdas[low] -= p_lambda
            contrib = jnp.where(hi_is_i, p_lambda, -p_lambda)
            lam = -jnp.sum(contrib, axis=0)
            hes = jnp.sum(p_hess, axis=0)
            lam = lam.at[:t].add(jnp.sum(contrib, axis=1))
            hes = hes.at[:t].add(jnp.sum(p_hess, axis=1))
            if norm:
                sum_lambdas = -2.0 * jnp.sum(p_lambda, axis=(0, 1))
                factor = jnp.where(sum_lambdas > 0, jnp.log2(1.0 + sum_lambdas) /
                                   jnp.maximum(sum_lambdas, EPS), 1.0)
                lam = lam * factor[None, :]
                hes = hes * factor[None, :]
            return lam, hes

        def bucket(s, disc, rows, n_valid, lab, gain, inv_max_dcg, t):
            m, q = s.shape
            s = jnp.where(s == 0, 0.0, s)            # -0.0 ties with 0.0, as in C++
            # descending, stable: padding (KMIN_SCORE) sorts last
            neg, rows, sl, sg = jax.lax.sort((-s, rows, lab, gain), dimension=0, num_keys=1,
                                             is_stable=True)
            ss = -neg
            chunk = max(1, min(q, PAIR_CHUNK_ELEMENTS // (t * m)))
            if chunk >= q:
                lam, hes = pair_block(ss, sl, sg, n_valid, inv_max_dcg, disc, t)
            else:
                chunk = max(LANES, chunk // LANES * LANES)
                nch = -(-q // chunk)
                pad = nch * chunk - q
                cols = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))
                ss, sl, sg, n_valid, inv = map(cols, (ss, sl, sg, n_valid, inv_max_dcg))

                def step(k, acc):
                    at = k * chunk
                    part = lambda a: jax.lax.dynamic_slice_in_dim(a, at, chunk, a.ndim - 1)
                    lk, hk = pair_block(part(ss), part(sl), part(sg), part(n_valid),
                                        part(inv), disc, t)
                    return (jax.lax.dynamic_update_slice_in_dim(acc[0], lk, at, 1),
                            jax.lax.dynamic_update_slice_in_dim(acc[1], hk, at, 1))

                zero = jnp.zeros((m, nch * chunk), jnp.float32)
                lam, hes = jax.lax.fori_loop(0, nch, step, (zero, zero))
                lam, hes = lam[:, :q], hes[:, :q]
            return rows.reshape(-1), lam.reshape(-1), hes.reshape(-1)

        shapes = [v.shape for v in layout.valid]

        @jax.jit
        def grad_fn(score, fwd, buckets, disc):
            with jax.named_scope("lgbm.rank.lambdas"):
                n = score.shape[0]
                ext = jnp.concatenate([score, jnp.full((fwd.shape[0] - n,), KMIN_SCORE,
                                                       score.dtype)])
                _, flat = jax.lax.sort((fwd, ext), num_keys=1)
                parts, at = [], 0
                for (m, q), args, t in zip(shapes, buckets, tops):
                    parts.append(bucket(flat[at:at + m * q].reshape(m, q), disc, *args, t))
                    at += m * q
                rows, lam, hes = (jnp.concatenate(a) for a in zip(*parts))
                _, lam, hes = jax.lax.sort((rows, lam, hes), num_keys=1)
                return lam[:n], hes[:n]

        return grad_fn

    def get_gradients(self, score):
        return self._grad_fn(score, *self._bucket_args)


class RankXENDCG(RankingObjective):
    name = "rank_xendcg"

    def __init__(self, config):
        super().__init__(config)
        self.seed = int(config.objective_seed)
        self._iter = 0

    def check_label(self, label):
        if (label < 0).any():
            raise ValueError("ranking labels must be non-negative integers")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        idx, valid = pad_queries(self.query_boundaries)
        self.q_idx = jnp.asarray(idx)
        self.q_valid = jnp.asarray(valid)
        self._grad_fn = self._build_grad_fn()

    def _build_grad_fn(self):
        @jax.jit
        def grad_fn(score, label, q_idx, q_valid, key):
            n = score.shape[0]
            q, m = q_idx.shape
            s = jnp.where(q_valid, score[q_idx], KMIN_SCORE)
            lab = label[q_idx]
            gammas = jax.random.uniform(key, (q, m))

            # per-query softmax over valid docs
            smax = jnp.max(s, axis=1, keepdims=True)
            es = jnp.where(q_valid, jnp.exp(s - smax), 0.0)
            rho = es / jnp.maximum(jnp.sum(es, axis=1, keepdims=True), EPS)

            phi = jnp.where(q_valid, jnp.exp2(jnp.floor(lab)) - gammas, 0.0)
            inv_den = 1.0 / jnp.maximum(jnp.sum(phi, axis=1, keepdims=True), EPS)

            # first-order terms (rank_objective.hpp:330-338)
            t1 = -phi * inv_den + rho
            params1 = jnp.where(q_valid, t1 / jnp.maximum(1.0 - rho, EPS), 0.0)
            sum_l1 = jnp.sum(params1, axis=1, keepdims=True)
            # second-order
            t2 = rho * (sum_l1 - params1)
            params2 = jnp.where(q_valid, t2 / jnp.maximum(1.0 - rho, EPS), 0.0)
            sum_l2 = jnp.sum(params2, axis=1, keepdims=True)
            lam = t1 + t2 + rho * (sum_l2 - params2)
            hess = rho * (1.0 - rho)
            # queries with <2 docs get zero gradients
            few = (jnp.sum(q_valid, axis=1, keepdims=True) <= 1)
            lam = jnp.where(few | ~q_valid, 0.0, lam)
            hess = jnp.where(few | ~q_valid, 0.0, hess)

            grad = jnp.zeros((n,), jnp.float32).at[q_idx.reshape(-1)].add(
                lam.reshape(-1), mode="drop")
            hs = jnp.zeros((n,), jnp.float32).at[q_idx.reshape(-1)].add(
                hess.reshape(-1), mode="drop")
            return grad, hs

        return grad_fn

    def get_gradients(self, score):
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), self._iter)
        self._iter += 1
        return self._grad_fn(score, self.label, self.q_idx, self.q_valid, key)
