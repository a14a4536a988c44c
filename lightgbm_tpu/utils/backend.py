"""Which backend this process runs on — asked once, never papered over.

The kernels, the grower choice, the dense predictor and the histogram
autotune all branch on "is this a TPU?".  The CPU is a legitimate
answer only when somebody asked for it (``JAX_PLATFORMS=cpu``: the test
suite, CI).  An accelerator that fails to initialise, or a JAX that
found none and settled on the CPU by itself, is an error here: a run
that was meant for the chip must not finish somewhere else under the
same metric names.
"""

from __future__ import annotations

import jax

_resolved: str | None = None


def default_backend() -> str:
    """``jax.default_backend()``, cached after the first answer (the
    backend cannot change once a client is live).

    Raises ``RuntimeError`` when the accelerator cannot be initialised
    (JAX's own error passes through), and when JAX fell back to the CPU
    without ``JAX_PLATFORMS`` naming it.
    """
    global _resolved
    if _resolved is None:
        # chaos layer: an armed device_loss fault makes the probe behave
        # exactly like a lost accelerator (resilience/faults.py)
        from ..resilience.faults import faults
        faults.check_device_probe()
        backend = jax.default_backend()
        if backend == "cpu" and "cpu" not in (jax.config.jax_platforms or ""):
            raise RuntimeError(
                "JAX found no accelerator and fell back to the CPU by "
                "itself; lightgbm_tpu only runs on the CPU when asked to: "
                "set JAX_PLATFORMS=cpu to do that on purpose")
        _resolved = backend
    return _resolved
