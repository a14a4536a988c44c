"""Where this checkout keeps what it compiles and measures once.

One root for JAX's persistent compilation cache, the histogram autotune
winners (learner/autotune.py) and the natively built host helpers
(utils/native.py).  The root can be placed from outside with
``JAX_COMPILATION_CACHE_DIR`` — JAX reads that variable itself at
import, so when it is set nothing is configured in code.  Otherwise the
root is ``<checkout>/.jax_cache``, a fixed path next to the package:
the directory is part of the cache key, so a path that moves between
runs never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_root() -> str:
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_root`
    and return that directory.  Entry points call this once before the
    first compile (chip_smoke.py, bench.py, benchmarks/, the CLI,
    tests/conftest.py); a library import configures nothing."""
    root = cache_root()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", root)
    return root
