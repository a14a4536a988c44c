"""From a profiler trace (``.xplane.pb``) to the few facts the per-layer
metrics read: when each device was busy, how long the named kernels took,
and what the host was doing in the longest gaps.

Benchmark code: every PR's trace is reduced by this file, and
``chipbench/tests`` checks it against a small recorded trace.

A trace is reduced to plain tuples first (:func:`read_events`), so the
arithmetic below never touches the profiler's objects:

* device events: ``(device, name, start_ns, end_ns)`` from each device plane's
  operation line; the name is the HLO instruction's text, which for a
  ``pallas_call`` starts with the kernel's own ``name=``;
* host spans: ``(name, start_ns, end_ns)`` of the harness's own
  ``TraceAnnotation``s, whose names start with ``chipbench.``.

The traced window is the host span ``chipbench.window``; device events are
clipped to it.
"""

from __future__ import annotations

import glob
import os

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(profile) -> tuple:
    """(device_events, host_spans) from a ``jax.profiler.ProfileData``."""
    device_events, host_spans = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            dev = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    device_events.append((dev, ev.name, int(ev.start_ns),
                                          int(ev.start_ns + ev.duration_ns)))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append((ev.name, int(ev.start_ns),
                                           int(ev.start_ns + ev.duration_ns)))
    return device_events, host_spans


def load(path: str) -> tuple:
    from jax.profiler import ProfileData
    return read_events(ProfileData.from_file(path))


def window_of(host_spans: list) -> tuple:
    """(start_ns, end_ns) of the traced window."""
    for name, lo, hi in host_spans:
        if name == WINDOW_SPAN:
            return lo, hi
    raise ValueError(f"trace has no {WINDOW_SPAN} span")


def clip(device_events: list, lo: int, hi: int) -> list:
    out = []
    for dev, name, s, e in device_events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((dev, name, s, e))
    return out


def merged_intervals(intervals: list) -> list:
    """Union of ``(start, end)`` intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def devices_of(device_events: list) -> list:
    return sorted({ev[0] for ev in device_events})


def matching_ns(device_events: list, needle: str) -> float:
    """Union of the intervals of events whose name contains ``needle``,
    averaged over the devices seen (a union, so an enclosing loop and the
    operations inside it are not counted twice)."""
    devs = devices_of(device_events)
    if not devs:
        return 0.0
    total = 0
    for d in devs:
        total += sum(e - s for s, e in merged_intervals(
            [(s, e) for dev, name, s, e in device_events if dev == d and needle in name]))
    return total / len(devs)


_CONTAINERS = (" while(", " conditional(", " call(")


def busy_ns(device_events: list) -> float:
    """Union of all operation intervals, averaged over the devices seen."""
    return matching_ns(device_events, "")


def short_name(label: str) -> str:
    """``%lgbm_hist_x.25 = s32[...] custom-call(...)`` -> ``lgbm_hist_x``: the
    instruction's name without its number, so that one kernel's calls add up."""
    name = label.split(" = ")[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def top_ops(device_events: list, k: int = 10) -> list:
    """[[name, seconds], ...]: the operations with most summed time.  Loops,
    conditionals and calls are left out: the operations inside them are on
    the same line of the trace and count themselves."""
    acc: dict = {}
    for _, name, s, e in device_events:
        if any(c in name for c in _CONTAINERS):
            continue
        key = short_name(name)
        acc[key] = acc.get(key, 0) + (e - s)
    best = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(device_events: list, host_spans: list, lo: int, hi: int, k: int = 10) -> list:
    """[[what the host was doing, seconds], ...] for the longest idle gaps of
    the busiest-known device (the first).  A gap is labelled by the innermost
    harness span that covers its middle, or ``outside-spans``."""
    devs = devices_of(device_events)
    if not devs:
        return []
    busy = merged_intervals([(s, e) for dev, _, s, e in device_events if dev == devs[0]])
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    inner = [sp for sp in host_spans if sp[0] != WINDOW_SPAN]

    def label(mid: int) -> str:
        best = None
        for name, s, e in inner:
            if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0][len(SPAN_PREFIX):] if best else "outside-spans"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    return [[label((s + e) // 2), (e - s) / 1e9] for s, e in longest]


class Reduced:
    """What the per-layer readers see of a trace."""

    def __init__(self, device_events: list, host_spans: list):
        self.window = window_of(host_spans)
        lo, hi = self.window
        self.host_spans = host_spans
        self.events = clip(device_events, lo, hi)
        self.window_s = (hi - lo) / 1e9
        self.busy_s = busy_ns(self.events) / 1e9

    def matching_s(self, needle: str) -> float:
        return matching_ns(self.events, needle) / 1e9

    def breakdown(self) -> dict:
        lo, hi = self.window
        return {"device_ops": top_ops(self.events),
                "idle_gaps": idle_gaps(self.events, self.host_spans, lo, hi)}
