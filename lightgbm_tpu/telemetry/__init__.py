"""lightgbm_tpu.telemetry — unified observability for training and serving.

Four pieces (see the module docstrings for depth):

  * :mod:`.metrics` — the process-wide :class:`MetricsRegistry` of
    counters / gauges / windowed histograms with labeled series;
    ``serve/stats.ModelStats`` reports into it.
  * :mod:`.trace` — hierarchical ``span("tree/wave/psum")`` host spans
    that always open a ``jax.profiler.TraceAnnotation`` (the profiler's
    session is the switch) and feed the chrome-trace export when the
    tracer is enabled.
  * :mod:`.train_record` — the per-run :class:`TrainRecord` (histogram
    passes per tree by kind, trace-time collective counts/bytes, XLA
    compile events, device-memory watermark, per-phase host dispatch time,
    set-up seconds), accumulated
    by ``models/gbdt.py`` and surfaced as ``Booster.train_record``.
  * :mod:`.export` — Prometheus text / JSON renderers; the serve HTTP
    server mounts ``GET /metrics``; ``python -m lightgbm_tpu profile``
    wraps a run in a ``jax.profiler.trace`` capture plus a dump.
  * :mod:`.slo` — declarative service-level objectives keyed to
    registry series, evaluated with multi-window burn-rate math
    (``GET /slo``, SLO-aware ``/healthz``, slowest-request exemplars).
  * :mod:`.flight` — the training flight recorder: a bounded ring of
    per-iteration events dumped to JSONL on crash/SIGTERM.

Master switch: ``enabled()`` / ``enable()`` / ``disable()`` (env
``LGBM_TPU_TELEMETRY=0`` to opt out).  Telemetry-on and telemetry-off
training produce bit-identical models — accumulation only observes.
"""

from ._config import enable, disable, enabled
from .metrics import (Counter, Gauge, MetricsRegistry, SlidingWindow,
                      WindowedHistogram, default_registry, percentile)
from .trace import Tracer, global_tracer, span, timed_span
from .train_record import (TrainRecord, collectives_reset,
                           collectives_snapshot, device_memory_peak,
                           last_train_record, note_collective,
                           set_last_train_record)
from .export import (PROMETHEUS_CONTENT_TYPE, render_json,
                     render_prometheus, write_snapshot)
from .slo import (SLO, SloEngine, all_slos, default_engine, slo)
from .flight import FlightRecorder

__all__ = [
    "enable", "disable", "enabled",
    "Counter", "Gauge", "MetricsRegistry", "SlidingWindow",
    "WindowedHistogram", "default_registry", "percentile",
    "Tracer", "global_tracer", "span", "timed_span",
    "TrainRecord", "collectives_reset", "collectives_snapshot",
    "device_memory_peak", "last_train_record", "note_collective",
    "set_last_train_record",
    "PROMETHEUS_CONTENT_TYPE", "render_json", "render_prometheus",
    "write_snapshot",
    "SLO", "SloEngine", "all_slos", "default_engine", "slo",
    "FlightRecorder",
]
