"""Test configuration: force an 8-device CPU mesh (SURVEY.md §4's
"multi-host simulated by multi-process/mesh-sharding on a single host" —
the reference's analog is test_dask.py's in-process multi-worker cluster).

Must run before any jax client is created.  The suite always runs on the
CPU (Pallas kernels in interpret mode); the chip is reached only through
``chip_smoke.py``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# Persistent compile cache: ~190 tests trigger hundreds of XLA:CPU
# compilations in one process; caching them on disk cuts repeat-run time
# drastically and reduces exposure to rare in-process compiler crashes
# observed after long compile sequences.  The directory comes from the
# one helper every entry point uses (JAX_COMPILATION_CACHE_DIR if set,
# else <checkout>/.jax_cache) ...
from lightgbm_tpu.utils.cache import configure_compile_cache

_cache_dir = configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
# ... and is exported to every subprocess tests spawn (gloo worker pairs,
# CLI entrypoint runs, fleet serve workers): each of those is a fresh jax
# that would otherwise recompile its whole program set per run.  jax
# reads these env spellings at import.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _cache_dir)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.0")

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_cache():
    """Release compiled executables after each test module.

    XLA:CPU maps every live compiled executable into the process; across
    ~190 tests the mapping count reaches vm.max_map_count (65530 default)
    and the NEXT compile segfaults (reproduced deterministically; maps
    measured at 64.5K right before SIGSEGV).  Clearing jit caches per
    module unmaps retired executables; the persistent compile cache
    makes any re-compile a cheap disk deserialize."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def binary_data():
    rng = np.random.RandomState(42)
    n = 600
    X = rng.randn(n, 6)
    logit = X[:, 0] * 2 + X[:, 1] - 0.5 * X[:, 2]
    y = (logit + 0.5 * rng.randn(n) > 0).astype(np.float64)
    return X, y


@pytest.fixture(scope="session")
def regression_data():
    rng = np.random.RandomState(17)
    n = 600
    X = rng.randn(n, 6)
    y = X[:, 0] * 3 + np.sin(2 * X[:, 1]) + 0.1 * rng.randn(n)
    return X, y


@pytest.fixture(scope="session")
def multiclass_data():
    rng = np.random.RandomState(7)
    n = 600
    X = rng.randn(n, 6)
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0.3).astype(int)).astype(np.float64)
    return X, y


@pytest.fixture(scope="session")
def rank_data():
    rng = np.random.RandomState(3)
    nq, qs = 40, 12
    y = rng.randint(0, 4, nq * qs).astype(np.float64)
    X = rng.randn(nq * qs, 5) + y[:, None] * 0.4
    group = np.full(nq, qs)
    return X, y, group


SMALL = {"num_leaves": 7, "min_data_in_leaf": 5, "verbosity": -1}


@pytest.fixture
def dma_everywhere(monkeypatch):
    """The chip's default kernel pipeline here too: a mesh takes the
    default (learner/serial.py ``wave_grow_kwargs``), which on the CPU is
    ``blockspec``."""
    from lightgbm_tpu.learner import serial
    from lightgbm_tpu.ops import histogram_pallas as hp
    monkeypatch.setattr(hp, "resolve_pipeline",
                        lambda pipeline=None: pipeline or "dma")
    serial._GROW_FN_CACHE.clear()
    yield
    serial._GROW_FN_CACHE.clear()
