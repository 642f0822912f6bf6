"""Tracing/profiling helpers: the program's span recorder, and the
window that joins its planes.

**The recorder.** Every layer boundary of the serving path (HTTP edge,
scheduler, cache manager, model step, transfers) records a span here:

    with span("istpu.sched.admit", request=rid, prompt_tokens=n) as f:
        ...
        f["outcome"] = "admitted"        # fields may be added inside
        f["dispatch_ns"] = elapsed_ns()  # ... a point inside the span too
    record("istpu.sched.queue_wait", t0_ns, dur_ns, request=rid)

``span`` enters a ``jax.profiler.TraceAnnotation`` (free while no
profiler session runs; a host event on the profiler's own clock while
one does, so program spans nest in the same xplane as the device
operations) and on exit appends one ``Span`` to a bounded process-wide
ring. It is always on: a ``with`` block, two clock reads and a deque
append. ``spans()`` snapshots the ring, ``chrome_trace()`` renders it
in the trace-event form the store's ``/trace`` uses.

Clocks. A span's start is ``time.time_ns()`` (CLOCK_REALTIME) and its
duration comes from ``perf_counter_ns``. The store's native spans are
on CLOCK_MONOTONIC, and the jax profiler's events (``ProfileData``
``start_ns``, the ``ts`` of its trace.json) count from the start of the
profiler session — neither unix nor monotonic time. ``clock_pair()``
ties the first two together; ``clock_offset_ns`` measures the third
from spans recorded both ways (ring and TraceAnnotation).

**The window.** The store side publishes native per-op latency
histograms (/stats, /metrics) AND — with ``ServerConfig(trace=True)`` /
``--trace`` / ``ISTPU_TRACE=1`` — per-worker span rings drained as
Chrome trace-event JSON (/trace; beyond the reference, which has only
ad-hoc chrono logs, ``infinistore.cpp:1114``); the engine side has the
ring above and jax's profiler. ``profile_window`` glues them for one
workload window:

    with profile_window(server, trace_dir="/tmp/tb", trace=True) as w:
        run_workload()
    print(w.op_deltas)      # store ops (and reclaim runs) in the window
    print(w.engine_spans)   # the program's spans that started in it
    print(w.trace_path)     # ONE Perfetto file, one time axis

``op_deltas`` subtracts the server's cumulative per-op COUNTERS across
the window — including the reclaim/read pipeline counters
(``reclaim_runs``, ``hard_stalls``, ``spills_cancelled``,
``promotes_async``, ``disk_reads_inline``), so a window shows whether
background reclaim or promotion ran inside it. Queue-depth GAUGES
(``spill_queue_depth``, ``promote_queue_depth``) are levels, not
counters — they land in ``window.gauges`` as (open, close) snapshots
instead of meaningless deltas. ``trace=True`` additionally drains
the store-side span rings at window close, clips them to the window
(both sides of the native plane share CLOCK_MONOTONIC) and merges them
and the engine's spans into the jax profiler timeline, shifted onto
its axis, as a single Perfetto-loadable file.
"""

import bisect
import collections
import glob
import gzip
import itertools
import json
import os
import statistics
import threading
import time
from contextlib import contextmanager

# ---------------------------------------------------------------------------
# The span recorder
# ---------------------------------------------------------------------------

RING_SPANS = 65536
_PAIR_WITHIN_NS = 2_000_000  # clock_offset_ns: a pair is this close

# One record of the ring. `id` is unique in the process; `parent` is the
# id of the span that enclosed this one on its thread (0: none);
# `t0_ns` is time.time_ns() at entry, `dur_ns` a perf_counter_ns
# difference; `request` and `engine` are inherited from the enclosing
# span where the caller gave none; `fields` is the caller's dict.
Span = collections.namedtuple(
    "Span", "id parent name t0_ns dur_ns tid request engine fields")

_ring = collections.deque(maxlen=RING_SPANS)
_span_ids = itertools.count(1)
_engine_ids = itertools.count(1)
_tls = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, bound at first span
_listening = False  # the compile listener is registered


def _stack():
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


def _bind_annotation():
    # jax stays out of this module's import: the store's CLI child
    # imports the package and must not load it.
    global _annotation
    import jax

    _annotation = jax.profiler.TraceAnnotation
    return _annotation


def _inherit(stack, request, engine):
    """(parent id, request, engine) of a span opened under `stack`:
    what the caller left None comes from the enclosing span."""
    if not stack:
        return 0, request, engine
    top = stack[-1]
    return (top._id, top.request if request is None else request,
            top.engine if engine is None else engine)


def next_engine_id():
    """A process-unique id for one engine's spans."""
    return next(_engine_ids)


class span:
    """Context manager recording one span; yields its `fields` dict so
    the block can add what it learns (an outcome, a byte count). Once
    it has closed, `dur_ns` is the duration it recorded: what a
    subclass that counts its spans' time adds up, at no clock read of
    its own (serving.py `_CauseSpan`)."""

    __slots__ = ("name", "request", "engine", "fields", "dur_ns", "_id",
                 "_parent", "_t0", "_p0", "_ann")

    def __init__(self, name, request=None, engine=None, **fields):
        self.name = name
        self.request = request
        self.engine = engine
        self.fields = fields

    def __enter__(self):
        stack = _stack()
        self._parent, self.request, self.engine = _inherit(
            stack, self.request, self.engine)
        self._id = next(_span_ids)
        stack.append(self)
        self._ann = (_annotation or _bind_annotation())(self.name)
        self._ann.__enter__()
        self._t0 = time.time_ns()
        self._p0 = time.perf_counter_ns()
        return self.fields

    def __exit__(self, *exc):
        dur = self.dur_ns = time.perf_counter_ns() - self._p0
        self._ann.__exit__(*exc)
        _stack().pop()
        _ring.append(Span(self._id, self._parent, self.name, self._t0, dur,
                          threading.get_ident(), self.request, self.engine,
                          self.fields))
        return False


def elapsed_ns():
    """ns since the innermost span open on this thread was entered: how
    a block marks a point inside its own span (a step's `dispatch_ns`)
    at the cost of one clock read."""
    return time.perf_counter_ns() - _stack()[-1]._p0


def record(name, t0_ns, dur_ns, request=None, engine=None, **fields):
    """Add a span after the fact (a wait is known only when it ends).
    Its parent is the span open on this thread, if any."""
    parent, request, engine = _inherit(_stack(), request, engine)
    _ring.append(Span(next(_span_ids), parent, name, int(t0_ns),
                      int(dur_ns), threading.get_ident(), request, engine,
                      fields))


def spans(since_ns=0):
    """Snapshot of the ring, oldest first: the spans that started at or
    after `since_ns` (unix ns)."""
    snap = list(_ring)
    return [s for s in snap if s.t0_ns >= since_ns] if since_ns else snap


def clock_pair():
    """One (CLOCK_REALTIME, CLOCK_MONOTONIC) reading in ns, taken back
    to back: what puts the store's monotonic spans and the ring's unix
    ones on one axis."""
    return (time.clock_gettime_ns(time.CLOCK_REALTIME),
            time.clock_gettime_ns(time.CLOCK_MONOTONIC))


def chrome_trace():
    """The ring as Chrome trace-event JSON (the object form the store's
    /trace answers with): complete events, ts/dur in microseconds of
    unix time, one tid per recording thread. `metadata` carries one
    clock_pair() so a reader can shift the store's CLOCK_MONOTONIC
    spans onto the same axis."""
    real_ns, mono_ns = clock_pair()
    return {
        "displayTimeUnit": "ms",
        "metadata": {"clock_realtime_ns": real_ns,
                     "clock_monotonic_ns": mono_ns},
        "traceEvents": _trace_events(spans(), 0),
    }


def _trace_events(ring_spans, shift_ns, pid=None):
    pid = os.getpid() if pid is None else pid
    out = []
    for s in ring_spans:
        args = {"id": s.id, "parent": s.parent}
        if s.request is not None:
            args["request_id"] = s.request
        if s.engine is not None:
            args["engine"] = s.engine
        args.update(s.fields)
        out.append({"ph": "X", "pid": pid, "tid": s.tid, "name": s.name,
                    "ts": (s.t0_ns - shift_ns) / 1e3,
                    "dur": s.dur_ns / 1e3, "args": args})
    return out


def clock_offset_ns(ring_spans, trace_events):
    """Offset between the ring's clock and a jax profiler session's,
    measured from spans recorded both ways: `trace_events` are
    (name, start_ns) of the session's host events. A first estimate
    comes from the names seen equally often on both sides, the k-th
    event matched with the k-th ring span (a span open when the session
    started or stopped is on one side only, and its name drops out);
    then every event is paired with the ring span of its name that
    starts nearest to it under that estimate, within _PAIR_WITHIN_NS.
    Returns (median of ring start - trace start, distance between the
    quartiles, pairs), or None without a pair."""
    trace_by_name = collections.defaultdict(list)
    for name, start_ns in trace_events:
        trace_by_name[name].append(start_ns)
    ring_by_name = collections.defaultdict(list)
    for s in ring_spans:
        if s.name in trace_by_name:
            ring_by_name[s.name].append(s.t0_ns)
    first = []
    for name, starts in trace_by_name.items():
        starts.sort()
        ring = ring_by_name[name]
        ring.sort()
        if len(ring) == len(starts):
            first += [r - t for r, t in zip(ring, starts)]
    if not first:
        return None
    estimate = statistics.median(first)
    diffs = []
    for name, starts in trace_by_name.items():
        ring = ring_by_name[name]
        for t in starts:
            i = bisect.bisect_left(ring, t + estimate)
            near = min(ring[max(0, i - 1):i + 1],
                       key=lambda r: abs(r - t - estimate), default=None)
            if near is not None \
                    and abs(near - t - estimate) <= _PAIR_WITHIN_NS:
                diffs.append(near - t)
    spread = 0
    if len(diffs) >= 2:
        q = statistics.quantiles(diffs, n=4)
        spread = q[2] - q[0]
    return int(statistics.median(diffs)), int(spread), len(diffs)


def compilations():
    """XLA executables this THREAD has built (or fetched from the
    persistent cache) so far: jax's monitoring event, counted by a
    listener registered once, on the compiling thread — so a step can
    tell what it compiled itself from what another engine did."""
    global _listening
    if not _listening:
        import jax

        def on_duration(event, _secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                _tls.compiled = getattr(_tls, "compiled", 0) + 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _listening = True
    return getattr(_tls, "compiled", 0)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

# Cumulative top-level stats COUNTERS worth windowing alongside the
# per-op table: traffic, the PR-3 reclaim pipeline counters and the
# PR-5 read pipeline counters (a window with nonzero reclaim_runs /
# disk_reads_inline explains its own tail).
_WINDOW_COUNTERS = (
    "bytes_in",
    "bytes_out",
    "reclaim_runs",
    "hard_stalls",
    "spills_cancelled",
    "evictions",
    "spills",
    "promotes",
    "promotes_async",
    "promotes_cancelled",
    "disk_reads_inline",
)

# Queue-depth GAUGES are LEVELS, not counters: deltaing them across the
# window (after - before) would report e.g. "-3 spills queued" when a
# busy queue drained, and 0 when a window entered and left equally
# backlogged — both meaningless. They are SNAPSHOT at both edges
# instead and land in ``window.gauges`` as (before, after) pairs.
_WINDOW_GAUGES = (
    "spill_queue_depth",
    "promote_queue_depth",
)


def _op_counts(stats):
    if isinstance(stats, list):  # ShardedConnection.stats(): per-shard
        merged = {}
        for shard in stats:
            for k, v in _op_counts(shard).items():
                merged[k] = merged.get(k, 0) + v
        return merged
    out = {}
    for op, s in (stats.get("op_stats") or {}).items():
        out[op] = int(s.get("count", 0))
    for key in _WINDOW_COUNTERS:
        out[key] = int(stats.get(key, 0))
    return out


def _gauge_levels(stats):
    """Current LEVEL of each windowed gauge (summed across shards for a
    ShardedConnection stats list)."""
    if isinstance(stats, list):
        merged = {}
        for shard in stats:
            for k, v in _gauge_levels(shard).items():
                merged[k] = merged.get(k, 0) + v
        return merged
    return {
        key: int(stats.get(key, 0))
        for key in _WINDOW_GAUGES
        if key in stats
    }


_MERGED_NAME = "merged.trace.json.gz"
_RING_PID = 9002  # the ring's track group in a merged file (the store's
#                   is 1; jax numbers its own from the planes)


WINDOW_SPAN = "istpu.profile.window"


class ProfileWindow:
    def __init__(self):
        self.op_deltas = {}
        # Queue-depth gauges, snapshot at both window edges:
        # {name: (level_at_open, level_at_close)} — levels, never
        # deltas (see _WINDOW_GAUGES).
        self.gauges = {}
        self.stats_before = {}
        self.stats_after = {}
        # The program's spans that started inside the window.
        self.engine_spans = []
        # trace=True outputs
        self.store_trace = None  # dict: {"traceEvents": [...]}
        self.trace_path = None   # merged Perfetto file on disk
        # (median, quartile distance, pairs) of ring clock - jax trace
        # clock in ns, where a merged file was aligned by it.
        self.clock_offset = None


def _store_trace_source(obj):
    """Find a store-side trace getter on ``obj`` (InfiniStoreServer
    exposes ``trace()``; anything duck-typed alike works)."""
    fn = getattr(obj, "trace", None)
    return fn if callable(fn) else None


def _merge_perfetto(trace_dir, store_events, engine_spans=(), clocks=None):
    """Merge the store spans and the engine's ring spans into the
    newest jax profiler trace under ``trace_dir`` (TensorBoard layout:
    plugins/profile/*/*.trace.json.gz) on ONE time axis; fall back to
    a file without the jax timeline when jax wrote nothing. Returns
    (merged file's path, clock_offset_ns(...) or None).

    The jax trace counts microseconds from the start of its session.
    The engine's spans are in it twice — as TraceAnnotation events on
    that clock and, with their fields, in the ring on unix time — so
    the matched pairs give the offset between the two
    (clock_offset_ns). ``clocks`` (one clock_pair()) carries the
    store's CLOCK_MONOTONIC spans to unix time, and the same offset
    then carries both onto the jax axis. Without a jax timeline the
    axis is the store's, and the ring's spans are shifted onto it."""
    merged = {"traceEvents": []}
    # Exclude our own output: a later window against the same trace_dir
    # must not pick a previous merged file as its "jax" base and
    # re-accumulate the earlier window's store spans.
    candidates = sorted(
        (
            p
            for p in glob.glob(
                os.path.join(trace_dir, "**", "*.trace.json.gz"),
                recursive=True,
            )
            if os.path.basename(p) != _MERGED_NAME
        ),
        key=os.path.getmtime,
    )
    if candidates:
        with gzip.open(candidates[-1], "rt") as f:
            merged = json.load(f)
        if not isinstance(merged.get("traceEvents"), list):
            merged["traceEvents"] = []
    real_ns, mono_ns = clocks or clock_pair()
    mono_to_unix_ns = real_ns - mono_ns
    offset = clock_offset_ns(engine_spans, [
        (ev["name"], ev["ts"] * 1e3) for ev in merged["traceEvents"]
        if ev.get("ph") == "X" and str(ev.get("name", "")).startswith(
            "istpu.")
    ])
    if offset is not None:
        ring_shift_ns = offset[0]
        store_shift_us = (mono_to_unix_ns - offset[0]) / 1e3
    else:  # no jax axis to land on: keep the store's
        ring_shift_ns = mono_to_unix_ns
        store_shift_us = 0.0
    for ev in store_events:
        if "ts" in ev:
            ev = dict(ev, ts=ev["ts"] + store_shift_us)
        merged["traceEvents"].append(ev)
    # The ring's copy carries what the annotations lack (request ids,
    # fields, parents); its own pid keeps it a separate track group.
    merged["traceEvents"].extend(
        _trace_events(engine_spans, ring_shift_ns, pid=_RING_PID))
    if engine_spans:
        merged["traceEvents"].append({
            "ph": "M", "pid": _RING_PID, "name": "process_name",
            "args": {"name": "istpu engine spans"}})
    out_path = os.path.join(trace_dir, _MERGED_NAME)
    with gzip.open(out_path, "wt") as f:
        json.dump(merged, f)
    return out_path, offset


@contextmanager
def profile_window(conn_or_server=None, trace_dir=None, trace=False):
    """Profile one workload window.

    conn_or_server: anything with ``.stats()`` (InfinityConnection,
        ShardedConnection or InfiniStoreServer) — per-op counter deltas
        land in ``window.op_deltas``. Optional.
    trace_dir: when set, wraps the window in ``jax.profiler`` so the
        device/XLA timeline lands there (TensorBoard/Perfetto format).
    trace: when True, also drain the STORE-side span rings at window
        close (requires ``conn_or_server`` to expose ``.trace()`` — an
        ``InfiniStoreServer`` whose config enables tracing; the rings
        live server-side, so a plain client cannot drain them) and
        merge them and the engine's spans with the jax trace into
        ``window.trace_path`` (``<trace_dir>/merged.trace.json.gz``,
        one time axis; ``window.store_trace`` always gets the span
        dict, even without a trace_dir).

    ``window.engine_spans`` always gets the program's own spans that
    started inside the window.
    """
    w = ProfileWindow()
    trace_fn = None
    if trace:
        trace_fn = _store_trace_source(conn_or_server)
        if trace_fn is None:
            raise ValueError(
                "profile_window(trace=True) needs an object with a "
                ".trace() method (InfiniStoreServer); clients cannot "
                "drain the server-side span rings"
            )
    if conn_or_server is not None:
        w.stats_before = conn_or_server.stats()
    # Window start on both clocks: the native spans' (CLOCK_MONOTONIC
    # µs — utils.cc now_us) and the ring's (unix ns). Entries from
    # before the window are clipped out of the merged export.
    t0_unix_ns, t0_mono_ns = clock_pair()
    t0_us = t0_mono_ns / 1e3
    tracing = False
    if trace_dir is not None:
        import jax

        jax.profiler.start_trace(str(trace_dir))
        tracing = True
    try:
        # One span that is surely on both clocks, whatever the workload
        # records: the merge measures the clock offset from it.
        with span(WINDOW_SPAN):
            yield w
    finally:
        if tracing:
            import jax

            jax.profiler.stop_trace()
        w.engine_spans = spans(since_ns=t0_unix_ns)
        if conn_or_server is not None:
            w.stats_after = conn_or_server.stats()
            before = _op_counts(w.stats_before)
            after = _op_counts(w.stats_after)
            w.op_deltas = {
                k: after.get(k, 0) - before.get(k, 0)
                for k in after
                if after.get(k, 0) != before.get(k, 0)
            }
            g0 = _gauge_levels(w.stats_before)
            g1 = _gauge_levels(w.stats_after)
            w.gauges = {
                k: (g0.get(k, 0), g1.get(k, 0))
                for k in sorted(set(g0) | set(g1))
            }
        if trace_fn is not None:
            full = trace_fn()
            events = [
                ev
                for ev in full.get("traceEvents", [])
                if ev.get("ph") == "M"
                or ev.get("ts", 0) + ev.get("dur", 0) >= t0_us
            ]
            w.store_trace = {"traceEvents": events}
            if trace_dir is not None:
                w.trace_path, w.clock_offset = _merge_perfetto(
                    str(trace_dir), events, w.engine_spans)


__all__ = [
    "ProfileWindow", "Span", "chrome_trace", "clock_offset_ns",
    "clock_pair", "compilations", "elapsed_ns", "next_engine_id",
    "profile_window", "record", "span", "spans",
]
