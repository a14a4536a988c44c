"""``python -m lightgbm_tpu`` — the CLI entry point (reference
src/main.cpp:11).  Tasks: train / predict / refit / convert_model via
``key=value`` args, plus the serving verb
``python -m lightgbm_tpu serve model.txt [port=8080 ...]``, the fleet
verb ``python -m lightgbm_tpu serve-fleet model.txt [workers=4 ...]``
(N supervised worker processes behind a crash-tolerant dispatcher), the
profiling verb ``python -m lightgbm_tpu profile config=train.conf``
(jax.profiler capture + telemetry dump) and the trace-lint verb
``python -m lightgbm_tpu lint-trace [configs=...] [out=report.json]``
(static analysis of the traced program matrix against the declared
collective/dtype/retrace/donation contracts; exits nonzero on any
violation)."""

import sys

from .cli import main

if __name__ == "__main__":
    sys.exit(main())
