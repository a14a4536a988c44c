"""Device time by the program's own phase names.

The program's jitted tree step carries ``jax.named_scope``s (``lgbm.quantize``,
``lgbm.wave.scan``, ...).  A scope is HLO metadata: it reaches the profiler's
trace as the ``tf_op`` stat of an operation's EVENT METADATA, the
instruction's ``op_name`` and type
(``jit(grow)/lgbm.endgame/while/body/lgbm.endgame.select/.../add:``).
``jax.profiler.ProfileData`` shows an event's own stats and not its
metadata's, and :mod:`chipbench.trace_reduce` keeps only names and times, so
this file reads the run's ``.xplane.pb`` again, as protobuf wire format (the
few fields of ``XSpace`` it needs, no generated code), and keeps that stat too.

A trace is reduced to plain tuples first, ``(device, name, scope, start_ns,
end_ns)``, where ``scope`` is the INNERMOST ``lgbm.`` component of the
``op_name`` or None; a fusion carries its root instruction's metadata, so it
is attributed by its root's scope.  The compiler's own operations (layout
copies, the slices and updates it splits off) carry no ``op_name``, and
neither do loops: :func:`fill_between` gives an unnamed operation the scope
of its neighbours in time where the named operation before it and the one
after it agree, and leaves it unnamed where they do not (the eager
operations between two trees sit between one tree's last phase and the next
one's first).  The arithmetic never touches the profiler's objects, and
``chipbench/tests/test_scope_reduce.py`` checks it on hand-made tuples and on
a slice of a real trace.

The six device parts partition ``non_hist_device_ms_per_tree`` (busy union
minus the union of ``lgbm_hist_*`` kernel events): loops, conditionals and
calls are left out as in :func:`chipbench.trace_reduce.top_ops` (the
operations inside them count themselves), events whose name contains
``lgbm_hist_`` are left out of every part, each scoped part is the union of
its events, and ``unscoped`` is what remains: device-busy time under no
``lgbm.`` scope, time inside a loop that no operation covers included.
A program without scopes (the parent of the PR that added them) gives no
parts at all: every reader returns None and its metric is left out.
"""

from __future__ import annotations

import glob
import os

from chipbench import trace_reduce as tr

SCOPE_STAT = "tf_op"            # the event-metadata stat that holds op_name
SCOPE_PREFIX = "lgbm."
KERNEL_NEEDLE = "lgbm_hist_"
UNSCOPED = "unscoped"

# part -> the scopes it reads (PERF.md section 3 has the table)
PARTS = {
    "grad_quant": ("lgbm.gradients", "lgbm.quantize"),
    "row_update": ("lgbm.wave.row_update", "lgbm.endgame.row_update"),
    "hist_glue": ("lgbm.root", "lgbm.ramp", "lgbm.wave.hist", "lgbm.endgame.hist"),
    "split_scan": ("lgbm.wave.child_out", "lgbm.wave.scan", "lgbm.wave.commit",
                   "lgbm.endgame", "lgbm.endgame.select"),
    "score_renew": ("lgbm.renew", "lgbm.score_update"),
}
PART_OF_SCOPE = {scope: part for part, scopes in PARTS.items() for scope in scopes}


def innermost_scope(op_name):
    """``jit(grow)/lgbm.endgame/while/body/lgbm.endgame.select/add`` ->
    ``lgbm.endgame.select``; None where no component starts with ``lgbm.``."""
    if not op_name:
        return None
    for part in reversed(str(op_name).split("/")):
        if part.startswith(SCOPE_PREFIX):
            return part
    return None


# ---- the few fields of an XSpace this file reads, from the wire ---------------
# XSpace.planes=1; XPlane: name=2 lines=3 event_metadata=4 stat_metadata=5 (maps:
# key=1 value=2); XLine: name=2 timestamp_ns=3 events=4; XEvent: metadata_id=1
# offset_ps=2 duration_ps=3; XEventMetadata: id=1 name=2 stats=5; XStatMetadata:
# id=1 name=2; XStat: metadata_id=1 str_value=5.

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane(buf) -> dict:
    """{"name", "lines": [(line name, timestamp_ns, [event views])],
    "event_names": {id: name}, "event_stats": {id: [stat views]},
    "stat_names": {id: name}} of one XPlane."""
    out = {"name": "", "lines": [], "event_names": {}, "event_stats": {}, "stat_names": {}}
    for f, v in _fields(buf):
        if f == 2:
            out["name"] = _text(v)
        elif f == 3:
            name, ts, events = "", 0, []
            for f2, v2 in _fields(v):
                if f2 == 2:
                    name = _text(v2)
                elif f2 == 3:
                    ts = v2
                elif f2 == 4:
                    events.append(v2)
            out["lines"].append((name, ts, events))
        elif f == 4:
            key, value = _map_entry(v)
            stats = []
            for f2, v2 in _fields(value):
                if f2 == 2:
                    out["event_names"][key] = _text(v2)
                elif f2 == 5:
                    stats.append(v2)
            out["event_stats"][key] = stats
        elif f == 5:
            key, value = _map_entry(v)
            out["stat_names"][key] = next((_text(v2) for f2, v2 in _fields(value) if f2 == 2), "")
    return out


def _event(view, timestamp_ns: int):
    """(metadata id, start_ns, end_ns), whole nanoseconds as ProfileData gives."""
    mid = offset = duration = 0
    for f, v in _fields(view):
        if f == 1:
            mid = v
        elif f == 2:
            offset = v
        elif f == 3:
            duration = v
    start = timestamp_ns + offset // 1000
    return mid, start, start + duration // 1000


def read_scoped_events(xspace: bytes, stat: str = SCOPE_STAT) -> tuple:
    """([(device, name, scope, start_ns, end_ns)], window) of a serialized
    XSpace: the device planes' operation lines, and the ``chipbench.window``
    span's (start_ns, end_ns) or None."""
    events, window = [], None
    for f, plane_buf in _fields(memoryview(xspace)):
        if f != 1:
            continue
        plane = _plane(plane_buf)
        names = plane["event_names"]
        if not plane["name"].startswith(tr.DEVICE_PLANE_PREFIX):
            wanted = {mid for mid, name in names.items() if name == tr.WINDOW_SPAN}
            for _, ts, line_events in plane["lines"] if wanted else ():
                for view in line_events:
                    mid, s, e = _event(view, ts)
                    if mid in wanted and window is None:
                        window = (s, e)
            continue
        dev = int(plane["name"][len(tr.DEVICE_PLANE_PREFIX):].split()[0])
        stat_ids = {sid for sid, name in plane["stat_names"].items() if name == stat}
        scope_of = {}
        for mid, stats in plane["event_stats"].items():
            for view in stats:
                fields = dict(_fields(view))
                if fields.get(1) in stat_ids and 5 in fields:
                    scope_of[mid] = innermost_scope(_text(fields[5]))
        for line_name, ts, line_events in plane["lines"]:
            if line_name != tr.OPS_LINE:
                continue
            for view in line_events:
                mid, s, e = _event(view, ts)
                events.append((dev, names.get(mid, ""), scope_of.get(mid), s, e))
    return events, window


def _is_container(name: str) -> bool:
    return any(c in name for c in tr._CONTAINERS)


def fill_between(events: list) -> list:
    """The events, an unnamed operation given the scope that the nearest named
    operation before it and the nearest after it, on its device, share.
    Loops, conditionals and calls neither give nor take a scope."""
    out = list(events)
    by_dev: dict = {}
    for i, ev in enumerate(out):
        if not _is_container(ev[1]):
            by_dev.setdefault(ev[0], []).append(i)
    for idx in by_dev.values():
        idx.sort(key=lambda i: (out[i][3], out[i][4]))
        before, last = [], None
        for i in idx:
            before.append(last)
            last = out[i][2] or last
        nxt = None
        for pos in range(len(idx) - 1, -1, -1):
            i = idx[pos]
            dev, name, scope, s, e = out[i]
            if scope is None and nxt is not None and before[pos] == nxt:
                out[i] = (dev, name, nxt, s, e)
            nxt = scope or nxt
    return out


def clip(events: list, lo: int, hi: int) -> list:
    out = []
    for dev, name, scope, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((dev, name, scope, s, e))
    return out


def _union_ns(intervals: list) -> int:
    return sum(e - s for s, e in tr.merged_intervals(intervals))


def parts_ns(events: list) -> dict:
    """{part: ns} averaged over the devices seen, ``unscoped`` included: the
    parts sum to the busy union minus the ``lgbm_hist_*`` union exactly.  A
    scope this file does not know counts as unscoped, so that it shows.  {}
    where no event carries a scope."""
    if not any(scope for _, _, scope, _, _ in events):
        return {}
    devs = sorted({ev[0] for ev in events})
    total = dict.fromkeys(list(PARTS) + [UNSCOPED], 0.0)
    for d in devs:
        mine = [ev for ev in events if ev[0] == d]
        busy = _union_ns([(s, e) for _, _, _, s, e in mine])
        kernels = _union_ns([(s, e) for _, name, _, s, e in mine if KERNEL_NEEDLE in name])
        named = 0
        for part in PARTS:
            ns = _union_ns([(s, e) for _, name, scope, s, e in mine
                            if PART_OF_SCOPE.get(scope) == part and KERNEL_NEEDLE not in name
                            and not _is_container(name)])
            total[part] += ns
            named += ns
        total[UNSCOPED] += busy - kernels - named
    return {part: ns / len(devs) for part, ns in total.items()}


def newest_xplane(root: str):
    """The newest ``.xplane.pb`` under ``<root>/.chipbench_trace/``: the one the
    run that calls this has just written.  None where there is none."""
    found = glob.glob(os.path.join(root, ".chipbench_trace", "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def run_parts(facts, reader_file: str) -> dict:
    """{part: ns} of the traced window of the run that ``facts`` belongs to,
    {} where there is nothing to read: no trace, a trace that is not this
    run's (its window differs), a program without scopes.  ``reader_file`` is
    the calling reader's ``__file__``, ``<root>/<path>/layer_metrics/x.py``:
    the run's root is found from it.  The file is read once a run: the parts
    are kept on ``facts``."""
    if facts.trace is None:
        return {}
    if not hasattr(facts, "scope_parts"):
        root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(reader_file))))
        path = newest_xplane(root)
        facts.scope_parts = {}
        if path is not None:
            with open(path, "rb") as fh:
                events, window = read_scoped_events(fh.read())
            # whole nanoseconds worked out as ProfileData works them out: equal or not ours
            if window is not None and tuple(window) == tuple(facts.trace.window):
                facts.scope_parts = parts_ns(clip(fill_between(events), *facts.trace.window))
    return facts.scope_parts


def part_ms_per_tree(facts, part: str, reader_file: str):
    """What a ``*_device_ms_per_tree`` reader returns."""
    k = facts.counters.get("traced_trees", 0)
    parts = run_parts(facts, reader_file) if k > 0 else {}
    if part not in parts:
        return None
    return parts[part] / 1e6 / k
