"""Synthetic insurance-claim rows in the one-hot coding of the upstream's
Allstate benchmark, a pure function of ``--seed``, handed over SPARSE: a
``scipy.sparse.csr_matrix`` (float32 values, int32 indices) whose columns are
the source's numeric columns as they are and one 0/1 indicator column for
every level of every coded source column.

What is kept of the public data set is its shape, not its rows: the source
columns in their order, which of them are coded and into how many levels
(they add up to the ``features`` columns of the coded file), skewed level
frequencies, makes > models > submodels nested, the share of zeros in the
numeric columns, a claim rate.  All of it comes from the configuration's
``data`` group; the problem (which level carries which effect, the numeric
columns' weights, the intercept that gives the claim rate) is drawn from
``weights_seed`` and is the same for every ``--seed``; the seed draws the rows.

* A numeric column follows its ``numeric`` law (``["geom", p, cap]`` 1 + a
  capped geometric count; ``["choice", first, k]`` one of k consecutive
  integers; ``["age", newest, scale, span]`` ``newest`` less a capped
  exponential age; ``["normal"]``; ``["zero_exp", share]`` zero with that
  share, else 1 + an exponential).  A zero is not stored.
* A coded column draws a RANK from its Zipf law (rank 0 the most frequent) and
  sets the indicator of level ``perm[rank]``, a fixed permutation of the
  column's levels: indicator columns are not sorted by frequency.  Exactly one
  indicator of a coded column is 1 in every row, so the columns of one source
  column are exactly exclusive.  A ``nested`` column's level follows from its
  child's (a submodel belongs to one model, a model to one make).
* ``label = uniform < sigmoid(intercept + signal * logit)``: ``logit`` the sum
  of the numeric columns' weighted standardised values and of an effect per
  level of every coded column.  The HELD-OUT labels are drawn by systematic
  sampling along the rows ordered by that probability (one uniform offset for
  the whole set): every row is still 1 with its own probability, and the
  positives are spread over the probability's range in proportion, so the
  held-out AUC of a 0.7%-positive label varies less from seed to seed than
  independent draws of about 7,300 positives would make it.

Blocks are ``datagen``'s: ``BLOCK_ROWS`` rows, block ``b`` drawn from
``SeedSequence(seed, spawn_key=(b,))``, so any block can be made again alone.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from .datagen import BLOCK_ROWS, map_blocks, worker_threads  # noqa: F401 (the drivers' too)

_KEY_WEIGHTS = 1_000_001      # spawn keys that no row block can have
_KEY_HOLDOUT = 1_000_002
_CALIBRATION_ROWS = 400_000   # rows the intercept is solved on (from weights_seed)


def _rng(seed: int, key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(key,)))


class SparseSpec:
    """The ``data`` group of a configuration file."""

    def __init__(self, data: dict):
        if data.get("generator") != "allstate_onehot_like":
            raise ValueError(f"unknown data generator {data.get('generator')!r}")
        self.rows = int(data["rows"])
        self.features = int(data["features"])
        self.holdout_rows = int(data["holdout_rows"])
        self.weights_seed = int(data["weights_seed"])
        self.zipf = float(data["zipf_exponent"])
        self.claim_rate = float(data["claim_rate"])
        self.signal = float(data["signal"])
        # source columns in the file's order: (name, levels); 0 levels = numeric
        self.columns = [(str(n), int(k)) for n, k in data["columns"]]
        self.numeric = {str(k): list(v) for k, v in data["numeric"].items()}
        self.nested = {str(k): str(v) for k, v in data.get("nested", {}).items()}
        self.num_names = [n for n, k in self.columns if k == 0]
        self.coded_names = [n for n, k in self.columns if k > 0]
        self.levels = {n: k for n, k in self.columns if k > 0}
        # first output column of every source column
        self.offset, at = {}, 0
        for n, k in self.columns:
            self.offset[n] = at
            at += max(k, 1)
        if at != self.features:
            raise ValueError(f"data: the columns code into {at} features, not {self.features}")
        if len(self.columns) != int(data["source_columns"]):
            raise ValueError("data: source_columns is not the number of columns")
        if set(self.num_names) != set(self.numeric):
            raise ValueError("data: numeric laws do not match the numeric columns")
        if self.rows <= 0 or self.holdout_rows <= 0:
            raise ValueError("data: rows, holdout_rows > 0")
        self.is_indicator = np.zeros(self.features, bool)
        for n in self.coded_names:
            self.is_indicator[self.offset[n]:self.offset[n] + self.levels[n]] = True
        self.n_num, self.n_coded = len(self.num_names), len(self.coded_names)

    @property
    def blocks(self) -> int:
        return -(-self.rows // BLOCK_ROWS)

    def block_range(self, b: int) -> tuple:
        lo = b * BLOCK_ROWS
        return lo, min(self.rows, lo + BLOCK_ROWS)


def _numeric(law: list, rng, n: int) -> np.ndarray:
    kind = law[0]
    if kind == "geom":
        return np.minimum(rng.geometric(float(law[1]), n), int(law[2])).astype(np.float32)
    if kind == "choice":
        return (int(law[1]) + rng.integers(0, int(law[2]), n)).astype(np.float32)
    if kind == "age":
        age = np.minimum(rng.exponential(float(law[2]), n), float(law[3]))
        return (int(law[1]) - np.floor(age)).astype(np.float32)
    if kind == "normal":
        return rng.standard_normal(n, dtype=np.float32)
    if kind == "zero_exp":
        v = 1.0 + rng.exponential(1.0, n)
        return np.where(rng.random(n) < float(law[1]), 0.0, v).astype(np.float32)
    raise ValueError(f"unknown numeric law {kind!r}")


class Tables:
    """The configuration's problem, the same for every seed: per coded column
    the rank law, the level permutation and the per-level effects; a nested
    column's parent table; the numeric columns' centres, spreads and weights;
    the intercept that gives the claim rate."""

    def __init__(self, spec: SparseSpec):
        rng = _rng(spec.weights_seed, _KEY_WEIGHTS)
        self.cdf, self.perm, self.effect, self.parent = {}, {}, {}, {}
        drawn = [n for n in spec.coded_names if n not in spec.nested]
        strength = np.sort(rng.exponential(1.0, spec.n_coded))[::-1] / np.sqrt(spec.n_coded)
        strength = dict(zip(spec.coded_names, strength[rng.permutation(spec.n_coded)]))
        for n in spec.coded_names:
            k = spec.levels[n]
            self.perm[n] = rng.permutation(k).astype(np.int32)
            self.effect[n] = (strength[n] * rng.standard_normal(k)).astype(np.float32)
            if n in drawn:
                w = 1.0 / np.arange(1, k + 1, dtype=np.float64) ** spec.zipf
                self.cdf[n] = np.cumsum(w / w.sum())
        for n, child in spec.nested.items():
            # level of ``n`` by level of ``child``: every level of n has a child
            k, kc = spec.levels[n], spec.levels[child]
            par = np.concatenate([np.arange(k), rng.integers(0, k, kc - k)])
            self.parent[n] = par[rng.permutation(kc)].astype(np.int32)
        # a column's turn: drawn columns, then each nested one after its child
        self.order = list(drawn)
        while len(self.order) < spec.n_coded:
            ready = [n for n in spec.coded_names
                     if n not in self.order and spec.nested[n] in self.order]
            if not ready:
                raise ValueError("data: nested columns do not lead back to a drawn one")
            self.order += ready
        num_s = np.sort(rng.exponential(1.0, spec.n_num))[::-1] / np.sqrt(spec.n_num)
        self.num_w = (num_s[rng.permutation(spec.n_num)] *
                      rng.choice([-1.0, 1.0], spec.n_num)).astype(np.float32)
        cal = _rng(spec.weights_seed, _KEY_HOLDOUT)
        vals = [_numeric(spec.numeric[n], cal, _CALIBRATION_ROWS) for n in spec.num_names]
        self.center = np.array([v.mean() for v in vals], np.float32)
        self.spread = np.array([max(float(v.std()), 1e-6) for v in vals], np.float32)
        self.intercept = 0.0
        _, _, logit = _draw(spec, _rng(spec.weights_seed, _KEY_HOLDOUT + 1),
                            _CALIBRATION_ROWS, self)
        lo, hi = -30.0, 10.0
        for _ in range(60):     # the intercept at which the mean probability is the claim rate
            mid = 0.5 * (lo + hi)
            if np.mean(1.0 / (1.0 + np.exp(-(logit + mid)))) < spec.claim_rate:
                lo = mid
            else:
                hi = mid
        self.intercept = 0.5 * (lo + hi)


def _draw(spec: SparseSpec, rng, n: int, t: Tables):
    """``n`` rows: numeric values float32 (n, n_num), the set indicator COLUMN
    of every coded column int32 (n, n_coded), both in the columns' order, and
    the logit float64 (n,)."""
    num = np.empty((n, spec.n_num), np.float32)
    logit = np.zeros(n, np.float64)
    for j, name in enumerate(spec.num_names):
        v = _numeric(spec.numeric[name], rng, n)
        num[:, j] = v
        logit += t.num_w[j] * ((v - t.center[j]) / t.spread[j])
    level = {}
    for name in t.order:
        if name in spec.nested:
            level[name] = t.parent[name][level[spec.nested[name]]]
        else:
            rank = np.minimum(np.searchsorted(t.cdf[name], rng.random(n)),
                              spec.levels[name] - 1)
            level[name] = t.perm[name][rank]
        logit += t.effect[name][level[name]]
    col = np.stack([spec.offset[name] + level[name] for name in spec.coded_names],
                   axis=1).astype(np.int32)
    return num, col, t.intercept + spec.signal * logit


def to_csr(spec: SparseSpec, num: np.ndarray, col: np.ndarray) -> sp.csr_matrix:
    """The rows as the coded file holds them: a numeric value where it is not
    zero, a 1 in the set indicator column of every coded column; indices in
    ascending order within a row."""
    n = len(num)
    src = np.empty((n, len(spec.columns)), np.int32)
    val = np.ones((n, len(spec.columns)), np.float32)
    jn = jc = 0
    for s, (name, k) in enumerate(spec.columns):
        if k == 0:
            src[:, s], val[:, s] = spec.offset[name], num[:, jn]
            jn += 1
        else:
            src[:, s] = col[:, jc]
            jc += 1
    keep = val != 0
    indptr = np.zeros(n + 1, np.int32)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    return sp.csr_matrix((val[keep], src[keep], indptr), shape=(n, spec.features))


def _labels(rng, logit: np.ndarray) -> np.ndarray:
    return (rng.random(len(logit)) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)


def block(spec: SparseSpec, seed: int, b: int, tables: Tables | None = None):
    """Training rows of block ``b``: (csr float32 (n, F), float32 (n,))."""
    lo, hi = spec.block_range(b)
    rng = _rng(seed, b)
    num, col, logit = _draw(spec, rng, hi - lo, tables or Tables(spec))
    return to_csr(spec, num, col), _labels(rng, logit)


def holdout(spec: SparseSpec, seed: int, tables: Tables | None = None):
    """The held-out rows: (csr float32 (H, F), float32 (H,)); the labels by
    systematic sampling along the rows in order of their probability."""
    rng = _rng(seed, _KEY_HOLDOUT)
    num, col, logit = _draw(spec, rng, spec.holdout_rows, tables or Tables(spec))
    p = 1.0 / (1.0 + np.exp(-logit))
    order = np.argsort(p, kind="mergesort")
    marks = np.floor(np.cumsum(p[order]) + rng.random())
    y = np.zeros(len(p), np.float32)
    y[order] = np.diff(marks, prepend=np.floor(marks[0] - p[order[0]])) > 0
    return to_csr(spec, num, col), y


def stack(blocks: list) -> sp.csr_matrix:
    """ONE csr matrix of the row blocks, int32 indices and index pointer (the
    training rows hold fewer than 2^31 stored values)."""
    sizes = np.array([0] + [b.nnz for b in blocks], np.int64).cumsum()
    if sizes[-1] >= 2 ** 31:
        raise ValueError("more stored values than int32 index pointers hold")
    indptr = np.concatenate([blocks[0].indptr[:1].astype(np.int32)] + [
        (b.indptr[1:].astype(np.int64) + off).astype(np.int32)
        for b, off in zip(blocks, sizes[:-1])])
    return sp.csr_matrix(
        (np.concatenate([b.data for b in blocks]),
         np.concatenate([b.indices for b in blocks]), indptr),
        shape=(sum(b.shape[0] for b in blocks), blocks[0].shape[1]))


def training_matrix(spec: SparseSpec, seed: int, tables: Tables | None = None):
    """(ONE csr float32 (rows, F), float32 (rows,)): the generator's blocks,
    made on a few threads and stacked."""
    t = tables or Tables(spec)
    made = map_blocks(spec, lambda b: block(spec, seed, b, t))
    y = np.concatenate([y for _, y in made])
    blocks = [x for x, _ in made]
    del made
    return stack(blocks), y


class Rows:
    """Sparse rows that ``len()`` and a row slice work on, as the drivers'
    ``predict_chunks`` takes its dense ones."""

    def __init__(self, csr: sp.csr_matrix):
        self.csr = csr

    def __len__(self) -> int:
        return self.csr.shape[0]

    def __getitem__(self, rows: slice) -> sp.csr_matrix:
        return self.csr[rows]
