"""Synthetic click-log rows in the raw Criteo schema, a pure function of
``--seed``: ``integer_columns`` count columns with missing values, then
``categorical_columns`` label-encoded categorical columns with missing
values, and a 0/1 click label.

What is kept of the public data set is its shape, not its rows: the column
counts, each categorical column's cardinality (capped at ``max_ids`` ids a
column: the rarer categories share one id, as every public pipeline's
min-count threshold makes them), Zipf frequencies over a column's categories,
the missing share of every column, a base click rate.  All of it comes from
the configuration's ``data`` group; the problem (which category carries
which effect, the integer columns' weights) is drawn from ``weights_seed`` and
is the same for every ``--seed``; the seed draws the rows.

* An integer column is ``floor(exp(N(m_j, s_j)))``, NaN with the column's
  missing share.  Its part of the logit is ``w_j * (log1p(v) - c_j) / d_j``,
  and ``w_j * miss_j`` where the value is missing.
* A categorical column draws a RANK from its Zipf law (rank 0 the most
  frequent; ranks past ``max_ids - 1`` fold into the last kept one) and
  stores ``id = (a * rank + b) mod K``: a fixed affine permutation of the
  column's K ids, the same for every seed, so ids are not sorted by frequency.
  Its part of the logit is an effect drawn per rank.  Ids are below 65,536
  and exact in float32; a missing category is NaN and carries an effect of
  its own.
* One numeric x categorical interaction: a second per-rank draw of one
  categorical column scales one integer column's standardised value.
* ``label = uniform < sigmoid(intercept + logit)``.

Blocks are ``datagen``'s: ``BLOCK_ROWS`` rows, block ``b`` drawn from
``SeedSequence(seed, spawn_key=(b,))``, so any block can be made again alone.
"""

from __future__ import annotations

import math

import numpy as np

from .datagen import BLOCK_ROWS, map_blocks, worker_threads  # noqa: F401 (the drivers' too)

_KEY_WEIGHTS = 1_000_001      # spawn keys that no row block can have
_KEY_HOLDOUT = 1_000_002


def _rng(seed: int, key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(key,)))


class CtrSpec:
    """The ``data`` group of a ``criteo_raw_like`` configuration."""

    def __init__(self, data: dict):
        if data.get("generator") != "criteo_raw_like":
            raise ValueError(f"unknown data generator {data.get('generator')!r}")
        self.rows = int(data["rows"])
        self.holdout_rows = int(data["holdout_rows"])
        self.n_int = int(data["integer_columns"])
        self.n_cat = int(data["categorical_columns"])
        self.features = int(data["features"])
        self.weights_seed = int(data["weights_seed"])
        self.max_ids = int(data["max_ids"])
        self.zipf = float(data["zipf_exponent"])
        self.cardinality = [int(c) for c in data["cardinality"]]
        self.cat_missing = [float(p) for p in data["cat_missing"]]
        self.int_missing = [float(p) for p in data["int_missing"]]
        self.int_log_mean = [float(v) for v in data["int_log_mean"]]
        self.int_log_sigma = [float(v) for v in data["int_log_sigma"]]
        self.int_center = [float(v) for v in data["int_center"]]
        self.int_spread = [float(v) for v in data["int_spread"]]
        self.int_weight = float(data["int_weight"])
        self.cat_weight = float(data["cat_weight"])
        self.interaction = dict(data["interaction"])
        self.intercept = float(data["intercept"])
        if self.features != self.n_int + self.n_cat:
            raise ValueError("data: features != integer_columns + categorical_columns")
        for name, want in (("cardinality", self.n_cat), ("cat_missing", self.n_cat),
                           ("int_missing", self.n_int), ("int_log_mean", self.n_int),
                           ("int_log_sigma", self.n_int), ("int_center", self.n_int),
                           ("int_spread", self.n_int)):
            if len(getattr(self, name)) != want:
                raise ValueError(f"data: {name} has {len(getattr(self, name))} entries, "
                                 f"not {want}")
        if self.rows <= 0 or self.holdout_rows <= 0 or not 2 <= self.max_ids <= 65536:
            raise ValueError("data: rows, holdout_rows > 0 and 2 <= max_ids <= 65536")

    @property
    def blocks(self) -> int:
        return -(-self.rows // BLOCK_ROWS)

    def block_range(self, b: int) -> tuple:
        lo = b * BLOCK_ROWS
        return lo, min(self.rows, lo + BLOCK_ROWS)

    @property
    def categorical_feature(self) -> list:
        """Column indices of the categorical columns."""
        return list(range(self.n_int, self.features))

    def ids(self, j: int) -> int:
        """How many ids categorical column ``j`` holds."""
        return min(self.cardinality[j], self.max_ids)


def rank_cdf(cardinality: int, kept: int, exponent: float) -> np.ndarray:
    """Cumulative Zipf probabilities of ranks 0..kept-1 of a column with
    ``cardinality`` categories; the ranks past ``kept - 1`` fold into the
    last kept one."""
    p = np.arange(1, cardinality + 1, dtype=np.float64) ** -exponent
    p /= p.sum()
    p = np.concatenate([p[:kept - 1], [p[kept - 1:].sum()]])
    cdf = np.cumsum(p)
    cdf[-1] = 1.0
    return cdf


def affine(j: int, k: int) -> tuple:
    """``(a, b)`` of column ``j``'s permutation ``id = (a * rank + b) mod k``:
    ``a`` the first number from about 0.618 k on that shares no factor with
    ``k`` (1 where k < 3)."""
    a = max(1, int(0.6180339887 * k))
    while math.gcd(a, k) != 1:
        a += 1
    return a, (7919 * (j + 1)) % k


class Tables:
    """The configuration's problem, the same for every seed: per categorical
    column the rank law, the id permutation and the per-rank effects; the
    integer columns' weights and missing values' contributions."""

    def __init__(self, spec: CtrSpec):
        rng = _rng(spec.weights_seed, _KEY_WEIGHTS)
        # a few columns carry most of the signal, as in a click log
        strength = lambda n: np.sort(rng.exponential(1.0, n))[::-1] / math.sqrt(n)
        self.int_w = spec.int_weight * strength(spec.n_int) * rng.choice([-1.0, 1.0], spec.n_int)
        self.int_w = self.int_w[rng.permutation(spec.n_int)].astype(np.float32)
        self.int_miss = (0.5 * rng.standard_normal(spec.n_int)).astype(np.float32)
        cat_s = spec.cat_weight * strength(spec.n_cat)
        cat_s = cat_s[rng.permutation(spec.n_cat)]
        self.cdf, self.perm, self.effect, self.cat_miss = [], [], [], []
        for j in range(spec.n_cat):
            k = spec.ids(j)
            self.cdf.append(rank_cdf(spec.cardinality[j], k, spec.zipf))
            self.perm.append(affine(j, k))
            self.effect.append((cat_s[j] * rng.standard_normal(k)).astype(np.float32))
            self.cat_miss.append(np.float32(cat_s[j] * rng.standard_normal()))
        it = spec.interaction
        self.inter_int, self.inter_cat = int(it["integer_column"]), int(it["categorical_column"])
        self.inter = (float(it["weight"]) * rng.standard_normal(
            spec.ids(self.inter_cat))).astype(np.float32)


def _draw(spec: CtrSpec, rng, n: int, t: Tables):
    """``n`` rows: float32 (n, features) and their 0/1 labels."""
    x = np.empty((n, spec.features), np.float32)
    logit = np.full(n, spec.intercept, np.float32)
    z_inter = None
    for j in range(spec.n_int):
        v = np.floor(np.exp(spec.int_log_mean[j] + spec.int_log_sigma[j] *
                            rng.standard_normal(n, dtype=np.float32)))
        miss = rng.random(n, dtype=np.float32) < spec.int_missing[j]
        z = (np.log1p(v) - np.float32(spec.int_center[j])) / np.float32(spec.int_spread[j])
        z = np.where(miss, t.int_miss[j], z).astype(np.float32)
        if j == t.inter_int:
            z_inter = np.where(miss, np.float32(0), z)
        logit += t.int_w[j] * z
        x[:, j] = np.where(miss, np.float32(np.nan), v)
    for j in range(spec.n_cat):
        k = spec.ids(j)
        rank = np.minimum(np.searchsorted(t.cdf[j], rng.random(n)), k - 1)
        miss = rng.random(n, dtype=np.float32) < spec.cat_missing[j]
        a, b = t.perm[j]
        logit += np.where(miss, t.cat_miss[j], t.effect[j][rank])
        if j == t.inter_cat:
            logit += np.where(miss, np.float32(0), t.inter[rank]) * z_inter
        x[:, spec.n_int + j] = np.where(miss, np.float32(np.nan),
                                        ((a * rank + b) % k).astype(np.float32))
    p = 1.0 / (1.0 + np.exp(-logit.astype(np.float64)))
    return x, (rng.random(n) < p).astype(np.float32)


def block(spec: CtrSpec, seed: int, b: int, tables: Tables | None = None):
    """Training rows of block ``b``: (float32 (n, F), float32 (n,))."""
    lo, hi = spec.block_range(b)
    return _draw(spec, _rng(seed, b), hi - lo, tables or Tables(spec))


def holdout(spec: CtrSpec, seed: int, tables: Tables | None = None):
    """The held-out rows: (float64 (H, F), float32 (H,))."""
    x, y = _draw(spec, _rng(seed, _KEY_HOLDOUT), spec.holdout_rows, tables or Tables(spec))
    return x.astype(np.float64), y


def training_blocks(spec: CtrSpec, seed: int, tables: Tables | None = None):
    """([float32 (n_b, F) per generator block], float32 (rows,)), made on a
    few threads; no block is ever joined to another."""
    t = tables or Tables(spec)
    made = map_blocks(spec, lambda b: block(spec, seed, b, t))
    return [x for x, _ in made], np.concatenate([y for _, y in made])
