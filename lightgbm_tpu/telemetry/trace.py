"""Hierarchical host-side span tracing with chrome-trace export.

``span("tree/wave/psum")`` contexts nest through a thread-local stack,
producing events whose names are full slash paths.  A span has two
halves:

* a ``jax.profiler.TraceAnnotation`` of the same path, ALWAYS entered.
  A ``TraceMe`` is inert unless a profiler session is running, so the
  profiler's session is the switch: whoever starts ``jax.profiler.trace``
  (the ``profile`` CLI verb, a benchmark's traced run) finds every span of
  the program on the device trace's own clock, with no environment
  variable and no argument.  With no session a span costs about a
  microsecond (``tests/test_telemetry.py::test_span_cost_without_session``
  holds it under 50);
* a Python-side event, recorded only while ``global_tracer.enabled``
  (``LGBM_TPU_TRACE=1`` or ``global_tracer.enable()``): the list that
  ``global_tracer.export_chrome_trace(path)`` writes in the
  ``chrome://tracing`` / Perfetto JSON array format.

``timed_span(store, key, name)`` is a span that also adds its host seconds
to ``store[key]`` (the set-up phases of ``Dataset.construct`` and
``GBDT``).  Host seconds of a span around code that only ENQUEUES device
work are dispatch time, not the work's time.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "global_tracer", "span", "timed_span", "in_span"]

_tls = threading.local()


class Tracer:
    """Collects (path, start, duration, thread) span events."""

    MAX_EVENTS = 1 << 20  # hard cap: a forgotten enable() can't eat RAM

    def __init__(self) -> None:
        self.enabled = os.environ.get("LGBM_TPU_TRACE", "0") == "1"
        self._lock = threading.Lock()
        self._events: List[Dict] = []
        self._t0 = time.perf_counter()
        self._dropped = 0

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self._dropped = 0
            self._t0 = time.perf_counter()

    def _record(self, path: str, start_s: float, dur_s: float,
                tid: int) -> None:
        ev = {"name": path,
              "ts": (start_s - self._t0) * 1e6,   # chrome trace wants us
              "dur": dur_s * 1e6,
              "ph": "X", "pid": os.getpid(), "tid": tid}
        with self._lock:
            if len(self._events) < self.MAX_EVENTS:
                self._events.append(ev)
            else:
                self._dropped += 1

    def events(self) -> List[Dict]:
        with self._lock:
            return list(self._events)

    def export_chrome_trace(self, path: str) -> int:
        """Write the collected spans as chrome-trace JSON; returns the
        event count written."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            doc["metadata"] = {"dropped_events": dropped}
        with open(path, "w") as fh:
            json.dump(doc, fh)
        return len(events)


global_tracer = Tracer()


class _Span:
    """Live span: pushes its name on the thread-local path stack, opens
    the profiler annotation, and records a Python-side event when the
    tracer is enabled."""

    __slots__ = ("name", "path", "_t0", "_ann")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self.name)
        self.path = "/".join(stack)
        self._ann = TraceAnnotation(self.path)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if global_tracer.enabled:
            global_tracer._record(self.path, self._t0, t1 - self._t0,
                                  threading.get_ident())
        _tls.stack.pop()
        return False


def span(name: str):
    """``with span("tree/grow"):`` — nested scope on the profiler's clock
    (always) and in the tracer's event list (when it is enabled)."""
    return _Span(name)


def in_span() -> bool:
    """Whether this thread is inside a span (a nested one then names
    itself relative to it)."""
    return bool(getattr(_tls, "stack", None))


class timed_span:
    """``with timed_span(store, "bin_find", "dataset/construct/sample"):``
    — a span whose host seconds are also added to ``store[key]``."""

    __slots__ = ("_store", "_key", "_span", "_t0")

    def __init__(self, store: Dict[str, float], key: str, name: str) -> None:
        self._store = store
        self._key = key
        self._span = _Span(name)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        self._store[self._key] = self._store.get(self._key, 0.0) + dt
        return False
