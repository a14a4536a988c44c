"""Synthetic query-document rows in the shape of a learning-to-rank data set
(Istella LETOR's train split), a pure function of ``--seed``.

What is kept of the public data set is its shape, not its rows: the number
of documents and of queries, a heavy-tailed law of query lengths, 220
float32 features of three kinds, graded labels 0-4 that are mostly 0.  All
of it comes from the configuration's ``data`` group.

* The QUERY LENGTHS are the configuration's, drawn once from
  ``weights_seed``: lognormal with the stated mean and sigma, rounded,
  clipped to ``[length_min, length_max]``, then scaled and adjusted by single
  documents to sum to exactly ``rows`` (every length stays inside the clip).
  The held-out queries' lengths are drawn from the same law, also from
  ``weights_seed``, and not adjusted.  So every seed has the same queries;
  ``--seed`` draws the documents and their labels.
* FEATURES, in this column order: ``query_features`` query-level columns
  (one value a query, repeated on its documents: lognormal), then
  ``count_features`` zero-inflated non-negative counts (zero with a
  per-column share, else 1 + a geometric count), then the rest continuous
  (standard normal plus a per-query shift of ``query_shift`` standard
  deviations, which a within-query order cannot see).
* LABELS: a document's latent relevance is a fixed random linear function of
  its standardised continuous columns and of the log of its counts, plus one
  interaction, plus a per-query effect, plus noise; label k where the latent
  lies between the calibrated quantiles of ``label_shares`` (calibrated on
  rows drawn from ``weights_seed``, so the shares hold in expectation).  A
  share ``no_relevant_share`` of the queries, drawn per query, have every
  label set to 0.
* BLOCKS are query-aligned: block ``b`` holds the queries whose first row
  falls in ``[b * BLOCK_ROWS, (b + 1) * BLOCK_ROWS)``, so no query straddles
  two blocks, and is drawn from ``SeedSequence(seed, spawn_key=(b,))``: any
  block can be made again alone.
"""

from __future__ import annotations

import numpy as np

from .datagen import BLOCK_ROWS, map_blocks, worker_threads  # noqa: F401 (the driver's too)

_KEY_WEIGHTS = 1_000_001      # spawn keys that no row block can have
_KEY_HOLDOUT = 1_000_002
_KEY_LENGTHS = 1_000_003
_KEY_HOLDOUT_LENGTHS = 1_000_004
_KEY_CALIBRATION = 1_000_005
_CALIBRATION_QUERIES = 500  # queries the label thresholds are set on


def _rng(seed: int, key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(key,)))


def lognormal_lengths(rng, n: int, mean: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """``n`` query lengths: lognormal of the given mean and sigma, rounded,
    clipped to [lo, hi]."""
    mu = np.log(mean) - 0.5 * sigma * sigma
    return np.clip(np.rint(rng.lognormal(mu, sigma, n)), lo, hi).astype(np.int64)


def adjust_total(lengths: np.ndarray, total: int, lo: int, hi: int, rng) -> np.ndarray:
    """Scale ``lengths`` to sum to about ``total``, then add or take single
    documents on queries drawn at random (with room inside [lo, hi]) until
    the sum is exact."""
    n = len(lengths)
    if not lo * n <= total <= hi * n:
        raise ValueError(f"{n} queries of {lo}..{hi} documents cannot hold {total}")
    out = np.clip(np.rint(lengths * (total / lengths.sum())), lo, hi).astype(np.int64)
    while out.sum() != total:
        d = int(total - out.sum())
        room = np.flatnonzero(out < hi) if d > 0 else np.flatnonzero(out > lo)
        pick = rng.choice(room, size=min(abs(d), len(room)), replace=False)
        out[pick] += 1 if d > 0 else -1
    return out


class RankSpec:
    """The ``data`` group of a configuration file, and the queries it fixes."""

    def __init__(self, data: dict):
        if data.get("generator") != "istella_letor_like":
            raise ValueError(f"unknown data generator {data.get('generator')!r}")
        self.rows = int(data["rows"])
        self.queries = int(data["queries"])
        self.features = int(data["features"])
        self.holdout_queries = int(data["holdout_queries"])
        self.length_mean = float(data["length_mean"])
        self.length_sigma = float(data["length_sigma"])
        self.length_min = int(data["length_min"])
        self.length_max = int(data["length_max"])
        self.n_query = int(data["query_features"])
        self.n_count = int(data["count_features"])
        self.n_cont = self.features - self.n_query - self.n_count
        self.count_zero_share = [float(v) for v in data["count_zero_share"]]
        self.query_shift = float(data["query_shift"])
        self.label_shares = np.asarray(data["label_shares"], np.float64)
        self.no_relevant_share = float(data["no_relevant_share"])
        self.query_effect = float(data["query_effect"])
        self.noise = float(data["noise"])
        self.interaction = float(data["interaction"])
        self.weights_seed = int(data["weights_seed"])
        if self.n_cont < 2 or self.n_query < 1 or self.n_count < 1:
            raise ValueError("data: query, count and >= 2 continuous features")
        if abs(self.label_shares.sum() - 1.0) > 1e-9 or len(self.label_shares) != 5:
            raise ValueError("data: label_shares are the shares of labels 0..4")
        rng = _rng(self.weights_seed, _KEY_LENGTHS)
        self.lengths = adjust_total(
            lognormal_lengths(rng, self.queries, self.length_mean, self.length_sigma,
                              self.length_min, self.length_max),
            self.rows, self.length_min, self.length_max, rng)
        self.holdout_lengths = lognormal_lengths(
            _rng(self.weights_seed, _KEY_HOLDOUT_LENGTHS), self.holdout_queries,
            self.length_mean, self.length_sigma, self.length_min, self.length_max)
        self.boundaries = np.concatenate([[0], np.cumsum(self.lengths)])
        # block b: the queries whose first row lies in [b, b + 1) * BLOCK_ROWS
        starts = np.searchsorted(self.boundaries[:-1], np.arange(0, self.rows, BLOCK_ROWS))
        self.block_queries = np.unique(np.r_[starts, self.queries])
        self.thresholds = _thresholds(self)

    @property
    def holdout_rows(self) -> int:
        return int(self.holdout_lengths.sum())

    @property
    def blocks(self) -> int:
        return len(self.block_queries) - 1

    def block_range(self, b: int) -> tuple:
        """Rows of block ``b``."""
        return (int(self.boundaries[self.block_queries[b]]),
                int(self.boundaries[self.block_queries[b + 1]]))

    def block_of_query(self, q: int) -> int:
        return int(np.searchsorted(self.block_queries, q, side="right") - 1)


class Weights:
    """The problem: the latent relevance's weights, the same for every seed."""

    def __init__(self, spec: RankSpec):
        rng = _rng(spec.weights_seed, _KEY_WEIGHTS)
        self.w_cont = rng.standard_normal(spec.n_cont) / np.sqrt(spec.n_cont)
        self.w_count = rng.standard_normal(spec.n_count) / np.sqrt(spec.n_count)
        self.w_query = rng.standard_normal(spec.n_query) / np.sqrt(spec.n_query)
        # geometric success probability of each count column
        self.count_p = rng.uniform(0.2, 0.7, spec.n_count)


def _draw(spec: RankSpec, rng, lengths: np.ndarray, w: Weights, thresholds=None):
    """Documents of the queries of ``lengths``: (float32 (n, F), float32
    labels (n,), or the latents where ``thresholds`` is None)."""
    nq, n = len(lengths), int(lengths.sum())
    of = np.repeat(np.arange(nq), lengths)
    x = np.empty((n, spec.features), np.float32)
    a, b = spec.n_query, spec.n_query + spec.n_count
    qf = rng.lognormal(0.0, 1.0, (nq, spec.n_query)).astype(np.float32)
    x[:, :a] = qf[of]
    zero = rng.random((n, spec.n_count), dtype=np.float32) < np.asarray(
        spec.count_zero_share, np.float32)
    counts = rng.geometric(w.count_p, (n, spec.n_count)).astype(np.float32)
    x[:, a:b] = np.where(zero, np.float32(0), counts)
    shift = rng.standard_normal((nq, 1), dtype=np.float32) * np.float32(spec.query_shift)
    cont = rng.standard_normal((n, spec.n_cont), dtype=np.float32)
    x[:, b:] = cont + shift[of]
    logc = np.log1p(x[:, a:b])
    latent = (cont @ w.w_cont.astype(np.float32)
              + logc @ w.w_count.astype(np.float32)
              + np.float32(spec.interaction) * np.sin(2 * cont[:, 0]) * cont[:, 1])
    qeff = (np.log(qf) @ w.w_query.astype(np.float32)) * np.float32(spec.query_effect)
    latent += qeff[of] + np.float32(spec.noise) * rng.standard_normal(n, dtype=np.float32)
    if thresholds is None:
        return x, latent
    y = np.searchsorted(thresholds, latent).astype(np.float32)
    none = rng.random(nq) < spec.no_relevant_share
    y[none[of]] = 0
    return x, y


def _thresholds(spec: RankSpec) -> np.ndarray:
    """The latent's quantiles at the cumulative label shares (4 numbers)."""
    rng = _rng(spec.weights_seed, _KEY_CALIBRATION)
    lengths = lognormal_lengths(rng, _CALIBRATION_QUERIES, spec.length_mean,
                                spec.length_sigma, spec.length_min, spec.length_max)
    _, latent = _draw(spec, rng, lengths, Weights(spec))
    return np.quantile(latent, np.cumsum(spec.label_shares)[:-1]).astype(np.float32)


def block(spec: RankSpec, seed: int, b: int, w: Weights | None = None):
    """Training rows of block ``b``: (float32 (n, F), float32 (n,))."""
    q0, q1 = spec.block_queries[b], spec.block_queries[b + 1]
    return _draw(spec, _rng(seed, b), spec.lengths[q0:q1], w or Weights(spec), spec.thresholds)


def training_blocks(spec: RankSpec, seed: int):
    """([float32 (n_b, F) per block], float32 (rows,)), made on a few
    threads; no block is ever joined to another."""
    w = Weights(spec)
    made = map_blocks(spec, lambda b: block(spec, seed, b, w))
    return [x for x, _ in made], np.concatenate([y for _, y in made])


def holdout(spec: RankSpec, seed: int):
    """The held-out queries: (float64 (H, F), float32 (H,), lengths (Qh,))."""
    x, y = _draw(spec, _rng(seed, _KEY_HOLDOUT), spec.holdout_lengths, Weights(spec),
                 spec.thresholds)
    return x.astype(np.float64), y, spec.holdout_lengths
