"""Per-training-run instrumentation: the ``TrainRecord``.

The communication-efficient parallel-GBDT literature (Meng et al. 2016;
Mitchell & Frank 2017) argues entirely through per-phase time and
per-pass communication volume; this repo used to reconstruct those
numbers by hand in PERF.md.  A ``TrainRecord`` accumulates them as the
boosting loop runs:

  * per-tree full-data histogram passes (``GrownTree.hist_passes``, the
    counter already asserted by tests/test_endgame.py), their kinds
    (``wave_passes``, ``endgame_passes``; the wave grower's
    ``1 + wave_passes + endgame_passes == hist_passes``), the splits the
    speculative ramp's verifying pass committed (``ramp_committed``),
    the rows the histogram kernels of those passes looped over
    (``hist_rows_contracted``: ``hist_passes * N`` padded rows unless
    the wave and endgame passes compacted theirs,
    ops/histogram_pallas.py; summed over the row shards this process
    holds), the rows in the tree's bag (``sampled_rows``: N where
    nothing samples, else the bagging mask's or the GOSS draw's count),
    how many of the tree's splits are categorical (``cat_splits``, read
    off the node records the grower returns), leaf counts, and the
    wave grower's own log of those passes (``passes``: each one's kind,
    leaves built, rows looped, active rows, compaction blocks and blocks
    that held an active row; ``ramp_sample_rows`` / ``ramp_sample_lanes``:
    the in-bag lanes of the speculative ramp's subsample) —
    kept as device scalars and pulled in batched, lazy fetches so the
    async dispatch pipeline never stalls;
  * the tree clock: when each tree's ``num_leaves`` reached the host
    (``done_s``, stamped after the one wait a boosting iteration has, the
    lagged stump check ``wait_prev``), what the iteration spent in that
    wait (``wait_s``) and outside it (``dispatch_s``); nothing is forced;
  * collective count and reduced bytes, tallied at the
    ``parallel/*.py`` collective call sites.  Those sites execute at
    TRACE time (the growers are jit/shard_map programs), so the tally
    is per *traced program* — the same quantity
    tests/test_specramp.py asserts by counting ``psum`` ops in the
    jaxpr — and a run that triggers no retrace adds nothing.  The DP
    wave path's merge mode is visible here: the full-batch psum tallies
    at ``data_parallel/wave/hist_psum``, the feature-sliced
    reduce-scatter records its 1/k received payload at
    ``data_parallel/wave/hist_reduce_scatter`` plus the tiny per-scan
    ``data_parallel/wave/winner_exchange`` (tests/test_wave_scatter.py
    asserts the >=4x per-pass byte drop at k=8);
  * XLA compile/retrace events via a ``jax.monitoring`` listener;
  * device-memory watermark via ``device.memory_stats()`` where the
    backend provides it (TPU does; CPU returns None);
  * per-phase HOST time (gradients / grow / record / eval): the boosting
    loop dispatches asynchronously, so ``phase_seconds["grow"]`` is the
    host's time to enqueue a tree (plus, on the first tree, to trace and
    compile the grower), not the tree's time on the device;
  * ``setup_seconds``: what the run spent before its first timed tree,
    by phase (see :meth:`TrainRecord.snapshot`).

Accumulation is gated by ``telemetry.enabled()`` and purely
observational: it reads values training already computed, so
telemetry-on and telemetry-off training produce bit-identical models
(asserted in tests/test_telemetry.py).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

from . import _config
from .trace import in_span, timed_span

__all__ = ["TrainRecord", "note_collective", "collectives_snapshot",
           "collectives_reset", "last_train_record",
           "set_last_train_record", "device_memory_peak",
           "note_hist_kernel", "hist_kernel_snapshot",
           "hist_kernel_reset"]


# ---------------------------------------------------------------------------
# Collective tally — incremented at TRACE time by the parallel strategies
# ---------------------------------------------------------------------------

_coll_lock = threading.Lock()
# site -> {"op": str, "count": int, "bytes": int, "operand_bytes": int,
#          "operand_sizes": [int per traced call, in order]}
_collectives: Dict[str, Dict[str, Any]] = {}


def _nbytes(value) -> int:
    try:
        nbytes = value.dtype.itemsize
        for d in value.shape:
            nbytes *= int(d)
        return int(nbytes)
    except Exception:
        return 0


def note_collective(site: str, op: str, value, operand=None) -> None:
    """Record one collective call site being traced.

    ``value`` is the payload the site is accounted by (concrete array or
    tracer — both expose shape/dtype); ``operand`` is what goes INTO the
    collective where that differs (a reduce-scatter is accounted by what
    each device receives, and its operand is the whole local batch).
    Called from inside jit/shard_map tracing, so this runs once per traced
    program, never per executed step; runtime cost of the compiled
    program is zero."""
    if not _config.enabled():
        return
    nbytes = _nbytes(value)
    operand_bytes = nbytes if operand is None else _nbytes(operand)
    with _coll_lock:
        rec = _collectives.get(site)
        if rec is None:
            rec = _collectives[site] = {"op": op, "count": 0, "bytes": 0,
                                        "operand_bytes": 0,
                                        "operand_sizes": []}
        rec["count"] += 1
        rec["bytes"] += nbytes
        rec["operand_bytes"] += operand_bytes
        rec["operand_sizes"].append(operand_bytes)


def collectives_snapshot() -> Dict[str, Dict[str, Any]]:
    with _coll_lock:
        return {k: dict(v, operand_sizes=list(v["operand_sizes"]))
                for k, v in _collectives.items()}


def collectives_reset() -> None:
    with _coll_lock:
        _collectives.clear()


# ---------------------------------------------------------------------------
# Histogram-kernel tally — incremented by the ops/histogram_pallas entry
# points.  Inside a jitted grower the entry wrapper runs at TRACE time
# (one tally per traced program, like the collective sites); on eager
# paths (autotune probes, benchmarks, the leaf-refit pass) it counts per
# build.  ``bytes`` is the kernel's streamed-byte estimate (bins +
# packed weights in, histogram block out) — the quantity the DMA
# pipeline and the 4-bit bin packing attack.
# ---------------------------------------------------------------------------

_hist_lock = threading.Lock()
# site -> {"count": int, "bytes": int[, "features", "contracted_features"]}
_hist_kernels: Dict[str, Dict[str, int]] = {}


def note_hist_kernel(site: str, streamed_bytes: int, features: int = 0,
                     contracted_features: int = 0) -> None:
    """``features`` / ``contracted_features``: the feature rows the kernel
    streams (its padded feature axis) and how many of them it one-hot
    encodes and contracts — the latest build's, where the kernel says."""
    if not _config.enabled():
        return
    with _hist_lock:
        rec = _hist_kernels.get(site)
        if rec is None:
            rec = _hist_kernels[site] = {"count": 0, "bytes": 0}
        rec["count"] += 1
        rec["bytes"] += int(streamed_bytes)
        if features:
            rec["features"] = int(features)
            rec["contracted_features"] = int(contracted_features)


def hist_kernel_snapshot() -> Dict[str, Dict[str, int]]:
    with _hist_lock:
        return {k: dict(v) for k, v in _hist_kernels.items()}


def hist_kernel_reset() -> None:
    with _hist_lock:
        _hist_kernels.clear()


# ---------------------------------------------------------------------------
# XLA compile / retrace events via jax.monitoring
# ---------------------------------------------------------------------------

_mon_lock = threading.Lock()
_mon_counts: Dict[str, int] = {}
_mon_registered = False


def _on_event(event: str, **kwargs) -> None:
    if not _config.enabled():
        return
    with _mon_lock:
        _mon_counts[event] = _mon_counts.get(event, 0) + 1


# The duration events JAX 0.9 emits around a jitted function's first call
# (jax/_src/dispatch.py), by the kind ``setup_seconds`` files them under.
# ``backend_compile_duration`` encloses ``compiler.compile_or_get_cached``,
# so a persistent-cache hit's ``/jax/compilation_cache/
# cache_retrieval_time_sec`` lies inside it and is not added again.
_SETUP_KIND_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_lower",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_trace_lower",
    "/jax/core/compile/backend_compile_duration": "compile_or_load",
}
# (kind, start, end) on the perf_counter clock.  An inner jit's trace event
# ends inside its caller's, so the sums are taken over the UNION of a kind's
# intervals, not over the durations.
_mon_intervals: collections.deque = collections.deque(maxlen=1 << 16)


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    if not _config.enabled():
        return
    end = time.perf_counter()
    with _mon_lock:
        _mon_counts[event] = _mon_counts.get(event, 0) + 1
        kind = _SETUP_KIND_OF_EVENT.get(event)
        if kind is not None:
            _mon_intervals.append((kind, end - float(duration), end))


def _union_seconds(intervals) -> float:
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _compile_seconds_by_kind(since: float, until: float) -> Dict[str, float]:
    """{"jax_trace_lower": s, "compile_or_load": s} of the events that
    ended between ``since`` and ``until`` (perf_counter clock)."""
    with _mon_lock:
        events = [e for e in _mon_intervals if since <= e[2] <= until]
    return {kind: _union_seconds([(lo, hi) for k, lo, hi in events
                                  if k == kind])
            for kind in ("jax_trace_lower", "compile_or_load")}


def _ensure_monitoring() -> None:
    """Register the jax.monitoring listeners once per process (listeners
    cannot be unregistered individually, so the callbacks themselves
    check the telemetry switch)."""
    global _mon_registered
    if _mon_registered:
        return
    with _mon_lock:
        if _mon_registered:
            return
        try:
            import jax.monitoring
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
        except Exception:
            pass  # older jax without monitoring: compile events stay empty
        _mon_registered = True


def _monitoring_snapshot():
    with _mon_lock:
        return dict(_mon_counts)


_COMPILE_MARKERS = ("compil", "trace", "jit")


def _compile_events(counts: Dict[str, int]) -> Dict[str, int]:
    return {k: v for k, v in counts.items()
            if any(m in k.lower() for m in _COMPILE_MARKERS)}


# ---------------------------------------------------------------------------
# Device memory watermark
# ---------------------------------------------------------------------------

def device_memory_peak() -> Optional[int]:
    """Max over devices of the backend's peak/in-use byte counter, or
    None when the backend exposes no memory_stats (XLA:CPU)."""
    try:
        import jax
        peak = None
        for d in jax.devices():
            stats = d.memory_stats() if hasattr(d, "memory_stats") else None
            if not stats:
                continue
            v = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
            if v is not None:
                peak = max(int(v), peak or 0)
        return peak
    except Exception:
        return None


# ---------------------------------------------------------------------------
# TrainRecord
# ---------------------------------------------------------------------------

class _Phase(timed_span):
    """A timed span into ``phase_seconds`` that also counts its calls.
    Inside ``train/iter`` the phase names itself relative to it."""

    __slots__ = ("_calls",)

    def __init__(self, rec: "TrainRecord", name: str) -> None:
        super().__init__(rec._phase_s, name,
                         name if in_span() else "train/" + name)
        self._calls = rec._phase_n

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._calls[self._key] = self._calls.get(self._key, 0) + 1
        return False


class _NoopPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_PHASE = _NoopPhase()

# the phases of a boosting iteration that enqueue its trees: a tree row's
# ``dispatch_s`` is their host seconds (``wait_prev`` is its ``wait_s``)
_DISPATCH_PHASES = ("gradients", "sample", "grow", "record")

_PASS_KEYS = ("kind", "leaves", "rows", "active_rows", "blocks",
              "blocks_active")


def _passes(log, rows_units, hist_passes: int) -> List[Dict[str, int]]:
    """A tree row's ``passes`` from the grower's log ``(shards, P, 6)``
    (learner/serial.py ``GrownTree.pass_log``): one dict a counted pass,
    ``kind`` and ``leaves`` as every shard states them, the four counts
    summed over the row shards, ``rows`` in rows (the log counts them in
    the unit ``hist_rows_contracted`` carries).  A tree with more passes
    than its log holds has the later ones' counts in the last entry."""
    import numpy as np
    log = np.asarray(log, np.int64)
    if not log.size:
        return []
    log = log.reshape((-1,) + log.shape[-2:])[:, :hist_passes]
    unit = np.reshape(rows_units, (-1, 2))[:, 1].astype(np.int64)
    counts = log[:, :, 2:].copy()
    counts[:, :, 0] *= unit[:, None]
    counts = counts.sum(axis=0)          # over the row shards
    return [dict(zip(_PASS_KEYS, map(int, (*log[0, i, :2], *counts[i]))))
            for i in range(log.shape[1])]


_FLUSH_EVERY = 256  # pending device scalars pulled per batched fetch


class TrainRecord:
    """Accumulates one training run's observability record.

    Created by ``GBDT._init_train`` and surfaced as
    ``Booster.train_record`` (a dict snapshot); the freshest record is
    also published process-wide for the ``/metrics`` exporter."""

    def __init__(self, meta: Optional[Dict[str, Any]] = None,
                 compile_since: Optional[float] = None,
                 mesh: Optional[Dict[str, Any]] = None,
                 grower: Optional[Dict[str, Any]] = None,
                 score_update: Optional[str] = None,
                 efb: Optional[Dict[str, Any]] = None) -> None:
        self._lock = threading.Lock()
        self.meta = dict(meta or {})
        self.mesh = dict(mesh or {})
        self.grower = dict(grower or {})
        self.efb = dict(efb or {})
        self.score_update = score_update
        self._t_created = time.perf_counter()
        # JAX's trace/lower/compile events count from here (perf_counter):
        # the start of the set-up the record belongs to, if it began earlier
        self._compile_since = self._t_created if compile_since is None \
            else compile_since
        self._compile_until: Optional[float] = None   # see end_of_update()
        self._phase_s: Dict[str, float] = {}
        self._phase_n: Dict[str, int] = {}
        # per-tree device scalars pending a batched host pull
        # (iteration, class_id, (hp, nl, wave, endgame, ramp_committed,
        #  hist_rows_contracted, sampled_rows, decision_type, pass_log,
        #  ramp_sample))
        self._pending: List[tuple] = []
        self._trees: List[Dict[str, Any]] = []
        # the tree clock: iteration -> {"done_s", "wait_s", "dispatch_s"}
        self._iter_clock: Dict[int, Dict[str, Optional[float]]] = {}
        self._clock_seen = (0.0, 0.0)   # (wait, dispatch) seconds booked
        self._setup_s: Dict[str, float] = {}
        self._mem_peak: Optional[int] = None
        self._coll_base = collectives_snapshot()
        self._hist_base = hist_kernel_snapshot()
        _ensure_monitoring()
        self._mon_base = _monitoring_snapshot()

    # -- accumulation (boosting loop) ------------------------------------
    def phase(self, name: str):
        """``with record.phase("grow"):`` — adds HOST time to the named
        phase and opens a ``train/<name>`` telemetry span.  Around
        asynchronous dispatch (``grow``, ``gradients``) that is the time
        to enqueue the work, not the work's time on the device."""
        if not _config.enabled():
            return _NOOP_PHASE
        return _Phase(self, name)

    def setup(self, key: str, name: str):
        """``with record.setup("upload", "train/init/upload"):`` — a span
        whose host seconds are added to ``setup_seconds[key]``."""
        if not _config.enabled():
            return _NOOP_PHASE
        return timed_span(self._setup_s, key, name)

    def end_of_update(self) -> None:
        """A boosting iteration has returned.  ``setup_seconds`` counts
        JAX's trace/lower/compile events up to the latest one, so what the
        caller compiles after training (a predictor) is not filed under
        the training run's set-up."""
        self._compile_until = time.perf_counter()

    def add_setup_seconds(self, seconds: Dict[str, float]) -> None:
        """Copy in set-up seconds kept elsewhere (the ``Dataset`` is built,
        and the learner lays out its matrix, outside this record)."""
        if not _config.enabled():
            return
        with self._lock:
            for k, v in seconds.items():
                self._setup_s[k] = self._setup_s.get(k, 0.0) + float(v)

    def tree_done(self, iteration: int) -> None:
        """Iteration ``iteration``'s ``num_leaves`` has just reached the
        host (the boosting loop's lagged stump check returned): its
        ``done_s``, the one clock read a tree costs."""
        if not _config.enabled():
            return
        done = time.perf_counter() - self._t_created
        with self._lock:
            self._iter_clock.setdefault(iteration, {})["done_s"] = done

    def end_of_iter(self, iteration: int) -> None:
        """Iteration ``iteration`` has enqueued its trees: its ``wait_s``
        and ``dispatch_s`` are what ``phase_seconds`` gained since the
        iteration before (``wait_prev``; gradients + sample + grow +
        record).  No clock is read."""
        if not _config.enabled():
            return
        with self._lock:
            now = (self._phase_s.get("wait_prev", 0.0),
                   sum(self._phase_s.get(k, 0.0) for k in _DISPATCH_PHASES))
            self._iter_clock.setdefault(iteration, {}).update(
                wait_s=now[0] - self._clock_seen[0],
                dispatch_s=now[1] - self._clock_seen[1])
            self._clock_seen = now

    def add_tree(self, iteration: int, class_id: int, hist_passes,
                 num_leaves, wave_passes=0, endgame_passes=0,
                 ramp_committed=0, hist_rows_contracted=((0, 0),),
                 sampled_rows=0, decision_type=(),
                 pass_log=((),), ramp_sample=((0, 0),)) -> None:
        """Record one grown tree.  The counts may be device scalars; they
        are NOT synced here — batches are pulled lazily so the async
        dispatch pipeline keeps flowing."""
        if not _config.enabled():
            return
        if not getattr(hist_rows_contracted, "is_fully_addressable", True):
            # a multi-process world: the row shards this process holds
            hist_rows_contracted, pass_log, ramp_sample = (
                [s.data for s in a.addressable_shards]
                for a in (hist_rows_contracted, pass_log, ramp_sample))
        if not getattr(sampled_rows, "is_fully_addressable", True):
            # a count over rows that span processes: this process's copy
            sampled_rows = sampled_rows.addressable_shards[0].data
        if not getattr(decision_type, "is_fully_addressable", True):
            decision_type = decision_type.addressable_shards[0].data
        with self._lock:
            self._pending.append((int(iteration), int(class_id),
                                  (hist_passes, num_leaves, wave_passes,
                                   endgame_passes, ramp_committed,
                                   hist_rows_contracted, sampled_rows,
                                   decision_type, pass_log, ramp_sample)))
            flush = len(self._pending) >= _FLUSH_EVERY
        if flush:
            self._flush()

    def note_memory(self) -> None:
        if not _config.enabled():
            return
        peak = device_memory_peak()
        if peak is not None:
            with self._lock:
                self._mem_peak = max(peak, self._mem_peak or 0)

    def _flush(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            return
        import numpy as np
        try:
            import jax
            vals = jax.device_get([p[2] for p in pending])
        except Exception:
            vals = [p[2] for p in pending]
        rows = [{"iteration": it, "class_id": cid,
                 "hist_passes": int(hp), "num_leaves": int(nl),
                 "wave_passes": int(wp), "endgame_passes": int(ep),
                 "ramp_committed": int(rc),
                 # (shards, 2) [count, unit] -> rows, over the shards
                 "hist_rows_contracted": sum(
                     int(c) * int(u) for c, u in np.reshape(rows, (-1, 2))),
                 "sampled_rows": int(sr),
                 # the grower's node records (models/tree.py: bit 0 of a
                 # node's decision_type says categorical), come with the
                 # same fetch
                 "cat_splits": int(np.count_nonzero(
                     np.asarray(dt, np.int64)[:max(int(nl) - 1, 0)] & 1)),
                 "passes": _passes(log, rows, int(hp)),
                 # (shards, 2) [in-bag lanes, lanes], over the shards
                 "ramp_sample_rows": int(np.reshape(ramp, (-1, 2))[:, 0].sum()),
                 "ramp_sample_lanes": int(np.reshape(ramp, (-1, 2))[:, 1].sum())}
                for (it, cid, _), (hp, nl, wp, ep, rc, rows, sr, dt, log, ramp)
                in zip(pending, vals)]
        with self._lock:
            self._trees.extend(rows)

    # -- snapshot --------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready record; pulls any pending device scalars (one
        batched fetch) and diffs the process-wide compile/collective
        tallies against this record's baseline.

        ``trees``: one row a tree.  Beside the counters the module's
        docstring lists, ``passes`` is the wave grower's log of the tree's
        counted passes in their order (``len == hist_passes``), each
        ``{"kind": 0 the first pass (the root's, or the ramp's verifying
        one) | 1 a wave | 2 an endgame pass, "leaves": leaves whose
        histograms it built, "rows": rows the leaf kernels looped over
        (they sum to ``hist_rows_contracted``), "active_rows": lanes that
        carried a channel, "blocks" / "blocks_active": the row
        compaction's blocks and those that held such a lane (a pass that
        was not compacted: every 8,192-lane block, all counted active)}``,
        the counts summed over the row shards; ``[]`` under a grower that
        keeps no log.  ``ramp_sample_rows`` / ``ramp_sample_lanes``: the
        in-bag lanes and all lanes of the speculative ramp's subsample (0
        with the ramp off).  The tree clock, host seconds since the record
        was made, the same three on every class row of an iteration:
        ``done_s``: when the tree's ``num_leaves`` reached the host, i.e.
        the END OF ITS GROWER on the device, not of its score update
        (which runs into the next tree's period); stamped by the next
        iteration after its one wait, so None for the newest tree and
        for a path that does not defer its trees (all three are None
        for a trainer that keeps no tree clock).  ``wait_s``: what the
        tree's own iteration spent blocked in that wait
        (``train/iter/wait_prev``: the host waiting for the device to
        finish the tree BEFORE).  ``dispatch_s``: the iteration's host
        seconds in gradients + sample + grow + record, what
        ``phase_seconds`` sums over the run.  ``phase_seconds`` itself
        stays host time of ENQUEUEING, whatever the device does.

        ``setup_seconds`` (host seconds; a phase that only enqueues device
        work reads its dispatch time): ``to_float64``, ``bin_find``,
        ``bin_matrix`` from ``Dataset.construct``; ``upload``
        (``GBDT._init_train`` placing bins, labels and scores: enqueues
        the copies), ``layout`` (the learner's one-time pad + transpose:
        enqueues), ``first_update`` (the first ``train_one_iter``: traces,
        lowers, compiles or loads every program of a tree, then enqueues
        it); and, of JAX's own ``jax.monitoring`` duration events since
        the record was created, ``jax_trace_lower``
        (``/jax/core/compile/jaxpr_trace_duration`` +
        ``/jax/core/compile/jaxpr_to_mlir_module_duration``) and
        ``compile_or_load`` (``/jax/core/compile/backend_compile_duration``,
        which encloses the persistent cache's
        ``/jax/compilation_cache/cache_retrieval_time_sec``), each the
        union of its events' intervals, up to the end of the latest
        boosting iteration; ``shard`` (``train/init/shard``, inside
        ``upload``: the bin codes going to the mesh as row shards).

        ``collectives`` (``collectives_traced`` before PR 28): per
        ``note_collective`` site, what was traced SINCE THIS RECORD WAS
        MADE: ``op``, ``count`` (traced call sites), ``bytes`` and
        ``operand_bytes`` (summed over them) and ``max_operand_bytes``
        (the largest single operand among them: at a histogram site, the
        batch one full wave pass merges).  A program served from the
        persistent compile cache is traced before the cache is asked, so
        its sites are here; a program found in the process's own jit
        cache (the same jitted function called again) is not traced
        again, and a record that saw no trace of a site leaves it out.
        ``mesh``: ``{"chips", "axis", "rows_per_chip"}``
        as the learner built it (one chip, no axis: the serial
        learner).  ``grower``: the wave grower's own statement of the
        static paths it was built with (learner/wave.py ``static_paths``:
        ``ramp``, ``endgame``, ``scatter``, ``voting``, ``efb``,
        ``any_cat``, ``row_update`` "kernel" | "xla", ``hist_acc_rows``),
        {} under a learner that grows some other way.  ``efb``: the
        bundling as the data set was built (efb.py ``BundleInfo.record``:
        ``features``, ``bundles``, ``bundle_bins``, ``bundled_features``,
        ``conflict_rows``, the rows in which a bundle's conflict overwrote
        a member's value, counted over all rows), {} where nothing was
        bundled.  ``score_update``:
        the lowering the booster's training-set score update took,
        ``"select"`` | ``"gather"`` (models/gbdt.py
        ``score_update_lowering``), None for a record no booster made."""
        self._flush()
        self.note_memory()  # final watermark: periodic samples miss the tail
        with self._lock:
            trees = list(self._trees)
            phase_s = dict(self._phase_s)
            phase_n = dict(self._phase_n)
            setup_s = dict(self._setup_s)
            mem_peak = self._mem_peak
            elapsed = time.perf_counter() - self._t_created
            clock = {it: dict(c) for it, c in self._iter_clock.items()}
        trees = [{**r, "done_s": None, "wait_s": None, "dispatch_s": None,
                  **clock.get(r["iteration"], {})} for r in trees]
        trees.sort(key=lambda r: (r["iteration"], r["class_id"]))
        coll_now = collectives_snapshot()
        coll = {}
        for site, rec in coll_now.items():
            base = self._coll_base.get(site, {})
            dc = rec["count"] - base.get("count", 0)
            if dc > 0:
                coll[site] = {
                    "op": rec["op"], "count": dc,
                    "bytes": rec["bytes"] - base.get("bytes", 0),
                    "operand_bytes": rec["operand_bytes"] -
                    base.get("operand_bytes", 0),
                    "max_operand_bytes": max(
                        rec["operand_sizes"][base.get("count", 0):])}
        hk_now = hist_kernel_snapshot()
        hist_kernels = {}
        for site, rec in hk_now.items():
            base = self._hist_base.get(site, {"count": 0, "bytes": 0})
            dc = rec["count"] - base["count"]
            db = rec["bytes"] - base["bytes"]
            if dc > 0:
                hist_kernels[site] = {**rec, "count": dc, "bytes": db}
        mon_counts = _monitoring_snapshot()
        events = {}
        for k, v in _compile_events(mon_counts).items():
            d = v - self._mon_base.get(k, 0)
            if d > 0:
                events[k] = d
        hp = [r["hist_passes"] for r in trees]
        setup_s.update(_compile_seconds_by_kind(
            self._compile_since, self._compile_until or time.perf_counter()))
        return {
            "schema": "train-record-v1",
            "meta": dict(self.meta),
            "num_trees": len(trees),
            "trees": trees,
            "hist_passes_total": sum(hp),
            "hist_passes_last": hp[-1] if hp else 0,
            "phase_seconds": {k: round(v, 6) for k, v in phase_s.items()},
            "phase_calls": phase_n,
            "setup_seconds": {k: round(v, 6) for k, v in setup_s.items()},
            "collectives": coll,
            "mesh": dict(self.mesh),
            "grower": dict(self.grower),
            "efb": dict(self.efb),
            "score_update": self.score_update,
            "hist_kernel": hist_kernels,
            "compile_events": events,
            "device_memory_peak_bytes": mem_peak,
            "elapsed_seconds": round(elapsed, 6),
        }


# ---------------------------------------------------------------------------
# Process-wide "last training run" handle (the /metrics exporter reads it)
# ---------------------------------------------------------------------------

_last_lock = threading.Lock()
_last_record: Optional[TrainRecord] = None


def set_last_train_record(rec: Optional[TrainRecord]) -> None:
    global _last_record
    with _last_lock:
        _last_record = rec


def last_train_record() -> Optional[TrainRecord]:
    with _last_lock:
        return _last_record
