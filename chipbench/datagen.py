"""Synthetic tabular data, a pure function of ``--seed``.

The Higgs-like generator of ``chip_smoke.py``/``bench.py`` (standard normal
features, a random linear logit plus one interaction, label noise), widened
to any feature count and cut into row blocks so that

* the training matrix is written once, in blocks, by a few threads (numpy's
  generators release the GIL), into one C-contiguous float64 array: the
  program converts its input to float64 anyway, and a float64 input makes
  that conversion free;
* any block can be made again from ``(seed, block)`` alone.  The plain
  reference uses that after the window: it never holds the whole matrix.

Feature values are float32-representable (the configuration states f32
features); block ``b`` is drawn from ``SeedSequence(seed, spawn_key=(b,))``.
All parameters come from the configuration's ``data`` group.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 262_144          # rows per generator block (fixed: part of the data's definition)
_KEY_WEIGHTS = 1_000_001      # spawn keys that no row block can have
_KEY_HOLDOUT = 1_000_002


def worker_threads() -> int:
    """Few threads, and never more than the host has."""
    return max(1, min(12, (os.cpu_count() or 1) - 1))


class TabularSpec:
    """The ``data`` group of a configuration file."""

    def __init__(self, data: dict):
        if data.get("generator") != "higgs_like":
            raise ValueError(f"unknown data generator {data.get('generator')!r}")
        self.rows = int(data["rows"])
        self.features = int(data["features"])
        self.holdout_rows = int(data["holdout_rows"])
        self.label_noise = float(data["label_noise"])
        self.interaction = float(data["interaction"])
        # the problem (the logit's weights) belongs to the configuration; the
        # seed draws the rows, so held-out quality differs from seed to seed
        # only by sampling
        self.weights_seed = int(data["weights_seed"])
        if self.rows <= 0 or self.features < 2 or self.holdout_rows <= 0:
            raise ValueError("data: rows, holdout_rows > 0 and features >= 2")

    @property
    def blocks(self) -> int:
        return -(-self.rows // BLOCK_ROWS)

    def block_range(self, b: int) -> tuple:
        lo = b * BLOCK_ROWS
        return lo, min(self.rows, lo + BLOCK_ROWS)


def _rng(seed: int, key: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(int(seed), spawn_key=(key,)))


def weights(spec: TabularSpec) -> np.ndarray:
    """The logit's weights: the configuration's, the same for every seed."""
    return _rng(spec.weights_seed, _KEY_WEIGHTS).standard_normal(spec.features) / \
        np.sqrt(spec.features)


def _draw(spec: TabularSpec, rng, n: int, w: np.ndarray):
    """``n`` rows: float32 features and their 0/1 labels."""
    x = rng.standard_normal((n, spec.features), dtype=np.float32)
    logit = x @ w.astype(np.float32)
    logit += np.float32(spec.interaction) * np.sin(2 * x[:, 0]) * x[:, 1]
    logit += np.float32(spec.label_noise) * rng.standard_normal(n, dtype=np.float32)
    return x, (logit > 0).astype(np.float32)


def block(spec: TabularSpec, seed: int, b: int, w: np.ndarray | None = None):
    """Training rows of block ``b``: (float32 (n, F), float32 (n,))."""
    lo, hi = spec.block_range(b)
    return _draw(spec, _rng(seed, b), hi - lo, weights(spec) if w is None else w)


def holdout(spec: TabularSpec, seed: int):
    """The held-out rows: (float64 (H, F), float32 (H,))."""
    x, y = _draw(spec, _rng(seed, _KEY_HOLDOUT), spec.holdout_rows, weights(spec))
    return x.astype(np.float64), y


def map_blocks(spec: TabularSpec, fn, threads: int | None = None) -> list:
    """``fn(b)`` for every block, on a few threads; results in block order."""
    with ThreadPoolExecutor(max_workers=threads or worker_threads()) as pool:
        return list(pool.map(fn, range(spec.blocks)))


def training_matrix(spec: TabularSpec, seed: int):
    """(float64 C-contiguous (rows, F), float32 (rows,)), written in blocks."""
    X = np.empty((spec.rows, spec.features), dtype=np.float64)
    y = np.empty(spec.rows, dtype=np.float32)
    w = weights(spec)

    def fill(b: int) -> None:
        lo, hi = spec.block_range(b)
        xb, yb = block(spec, seed, b, w)
        X[lo:hi] = xb
        y[lo:hi] = yb

    map_blocks(spec, fill)
    return X, y
