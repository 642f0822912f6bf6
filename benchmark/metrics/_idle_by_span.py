"""Every idle gap of the device given to what the program was doing in
it: the program's own spans (infinistore_tpu/utils/profiling.py, the
ring) laid on the device trace's clock.

benchmark/lib/trace.py hands each idle gap whole to ONE of the
benchmark's outside `bench.` spans, the one with the largest overlap
however small a share of the gap that is: an engine with nothing to
step for 0.95 s reads as 0.95 s of `bench.step`, because the step after
it overlaps the gap's last 50 ms (PERF.md, PR 38). Here a gap is SPLIT
by overlap among the innermost spans of the engine's own thread.

Four inputs, read once a run (the xplane once more, as _scoped_ops.py
does; lib/trace.py is not touched):

- the device operations and program (module) runs of every TensorCore
  plane inside `bench.trace_window`;
- the xplane's host events named `istpu.*`, ONLY to measure the ring's
  clock against the profiler's (`profiling.clock_offset_ns`);
- the ring's spans, shifted by that offset. The ring and not the
  annotations carries the spans: a span open when the session starts
  is on the ring alone (an `istpu.engine.no_work` spell can be seconds
  long), and only the ring has fields;
- the runtime's own host events around every program run
  (`DoEnqueueProgram`, `CompleteCallbacks`, matched to the plane's
  runs by `run_id`), ONLY to measure each device plane's clock against
  the host's: on the v5e host a plane's timestamps lie 0.6-1.7 ms
  EARLY, another amount every run (PERF.md, PR 38), which is the size
  of everything a decode step leaves idle. A run starts no earlier
  than its enqueue and ends no later than its completion is handled,
  which holds the skew between two bounds up to 0.17 ms apart, as the
  run's shortest programs fall, and in one run of eleven 0.016 ms ACROSS
  each other (`device_skew`; PERF.md, PR 38); their middle is taken,
  and half their distance goes on the `clock:` line as what the lead
  and the lag below are known to. It gates nothing.

Plane by plane: the plane's engine is the one whose `istpu.engine.step`
spans carry that chip's `device` index (four replicas: each plane
against its own engine's spans only). What no span of the engine's
thread covers is `loop` between two of the loop's own top-level spans
(step, submit, no_work: the delivery of finished requests, the locks)
and `unspanned` otherwise (the ring's edges, a plane without an
engine). `istpu.model.decode` / `.prefill` are split at their
`dispatch_ns` into `:dispatch` (host time the device waits for) and
`:wait` (device idle while the host waits for it: the return lag).

`joined(obs)` prints one line `clock: {...}` and one
`idle_by_program_span: {name: seconds of the window, mean over
planes}`, and gives None (so does every metric that reads it) where
fewer than MIN_PAIRS spans were recorded both ways or their offsets'
quartiles lie more than MAX_QUARTILE_NS apart, where there is no trace,
and where the ring does not reach back. It raises nothing.
"""

import bisect
import collections
import json
import time

from benchmark.lib import program_spans, serve, stats, trace
from benchmark.metrics import _scoped_ops, decode_host_p50_ms

MIN_PAIRS = 20
MAX_QUARTILE_NS = 100_000
# The runtime's host events around a program run, with a `run_id`.
ENQUEUED, COMPLETED = "DoEnqueueProgram", "CompleteCallbacks"
STEP, NO_WORK = "istpu.engine.step", "istpu.engine.no_work"
# What the HTTP loop itself records on the engine's thread, end to end.
LOOP_SPANS = (STEP, NO_WORK, "istpu.sched.submit")
# Recorded after the fact on the engine's thread: a wait, not work.
NOT_WORK = ("istpu.sched.queue_wait",)
SPLIT = ("istpu.model.decode", "istpu.model.prefill")
_UNREAD = object()


def read_plain(path):
    """The trace at `path` in lib/trace.py's plain form, with the host
    events this reader needs (the traced window's span and every
    `istpu.*` annotation) and, for the planes' clocks, "runs" beside a
    plane's modules ([[run_id, start_ns, dur_ns]]) and "launches"
    ({"<device ordinal>:<run_id>": [enqueue's start_ns, completion's
    start_ns]})."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": [], "launches": {}}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            dev = {"ops": [], "modules": [], "runs": []}
            for ln in plane.lines:
                key = {trace.OPS_LINE: "ops",
                       trace.MODULES_LINE: "modules"}.get(ln.name)
                if key is None:
                    continue
                for ev in ln.events:
                    at = [int(ev.start_ns), int(ev.duration_ns)]
                    dev[key].append([trace.short(ev.name)] + at)
                    if key == "modules":
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            dev["runs"].append([run] + at)
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == trace.WINDOW_SPAN \
                            or ev.name.startswith("istpu."):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
                    elif ev.name in (ENQUEUED, COMPLETED):
                        st = dict(ev.stats)
                        if "run_id" in st:
                            key = f"{st.get('device_ordinal', 0)}:" \
                                  f"{st['run_id']}"
                            out["launches"].setdefault(key, [None, None])[
                                ev.name == COMPLETED] = int(ev.start_ns)
    return out


def device_skew(runs, launches, ordinal):
    """What to ADD to the timestamps of the plane of device `ordinal`
    to put them on the host's clock: {"skew_ns", "halfwidth_ns",
    "runs"}, or None without a run the runtime's events name. Program
    run i cannot have started before the host began to enqueue it, nor ended after the host
    handled its completion: skew >= enqueue_i - start_i and skew <=
    completion_i - end_i for every i; the middle of the tightest pair
    is taken and half their distance says how well it is known (under
    0 the bounds cross, by the host events' own jitter or a drift
    inside the session: -0.008 ms seen, the middle holds as well)."""
    low = high = None
    n = 0
    for run, start, dur in runs:
        enqueued, completed = launches.get(f"{ordinal}:{run}", (None, None))
        if enqueued is None or completed is None:
            continue
        n += 1
        low = enqueued - start if low is None else max(low, enqueued - start)
        high = completed - start - dur if high is None \
            else min(high, completed - start - dur)
    if not n:
        return None
    return {"skew_ns": (low + high) // 2, "halfwidth_ns": (high - low) // 2,
            "runs": n}


def plain_of_run():
    """The newest run's trace (lib/cell.py's run directory), or None."""
    path = _scoped_ops._xplane()
    return None if path is None else read_plain(path)


def clock(plain, ring, closed_ns):
    """{"offset_ns", "quartile_distance_ns", "pairs"} of the ring's
    clock less the trace's, or None without a pair. `closed_ns`: the
    unix time at which lib/cell.py left the traced window's span, which
    is that span's end on the trace's clock. It only picks the ring's
    spans that started inside the session (the ring holds the whole
    run, and clock_offset_ns takes its first estimate from the names
    both sides hold equally often); the offset is measured from the
    pairs."""
    from infinistore_tpu.utils import profiling

    events = [(n, s) for n, s, _ in plain["host"] if n.startswith("istpu.")]
    w0, w1 = trace.window_of(plain)
    about = closed_ns - w1
    last = max([w1] + [s for _, s in events])
    found = profiling.clock_offset_ns(
        [s for s in ring if 0 <= s.t0_ns - about <= last], events)
    if found is None:
        return None
    return dict(zip(("offset_ns", "quartile_distance_ns", "pairs"), found))


def label(name):
    return "no_work" if name == NO_WORK else name


def timeline(spans, t0, t1):
    """[(start, end, name)], in order and without a hole, over [t0, t1):
    the innermost of `spans` (one thread's, on the trace's clock) at
    every instant, a SPLIT span cut at its `dispatch_ns`; what none
    covers is `loop` or `unspanned` (the module's docstring)."""
    out = []
    stack = []  # [end, name, where a SPLIT span's dispatch returned]
    at = t0  # how far `out` reaches
    last_top = None  # the name of the last top-level span that ended

    def emit(upto, name, cut=None):
        nonlocal at
        upto = min(upto, t1)
        if cut is not None and at < cut < upto:
            emit(cut, name + ":dispatch")
        if upto > at:
            if cut is not None:
                name += ":wait" if at >= cut else ":dispatch"
            out.append((at, upto, name))
            at = upto

    def close():
        nonlocal last_top
        end, name, cut = stack.pop()
        emit(end, label(name), cut)
        if not stack:
            last_top = name

    def hole(upto, then):
        own = last_top in LOOP_SPANS and then in LOOP_SPANS
        emit(upto, "loop" if own else "unspanned")

    for s in sorted(spans, key=lambda s: (s.t0_ns, -s.dur_ns)):
        while stack and stack[-1][0] <= s.t0_ns:
            close()
        if at >= t1:
            break
        end = s.t0_ns + s.dur_ns
        if stack:
            emit(s.t0_ns, label(stack[-1][1]), stack[-1][2])
            end = min(end, stack[-1][0])  # two clocks: a few hundred ns
        else:
            hole(s.t0_ns, s.name)
        cut = None
        if s.name in SPLIT and "dispatch_ns" in s.fields:
            cut = s.t0_ns + s.fields["dispatch_ns"]
        stack.append([end, s.name, cut])
    while stack:
        close()
    hole(t1, None)
    return out


def idle_gaps(dev, t0, t1):
    """The [start, end) in which the plane ran no operation, inside the
    window, as lib/trace.py's reduce takes them; None for a plane that
    ran nothing."""
    ops = list(trace._clip(dev["ops"] or dev["modules"], t0, t1))
    if not ops:
        return None
    edges = [t0] + [x for iv in trace._union((a, b) for _, a, b in ops)
                    for x in iv] + [t1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def split(gaps, segments, into):
    """Add to `into[name]` the ns of every gap under every segment."""
    j = 0
    for g0, g1 in gaps:
        while j < len(segments) and segments[j][1] <= g0:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < g1:
            a, b, name = segments[k]
            into[name] += min(b, g1) - max(a, g0)
            k += 1


def device_index(plane):
    tail = plane.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def engines(ring):
    """{engine id: (device index, the engine thread's spans)}: the
    thread is the one its steps were recorded on."""
    steps = collections.defaultdict(list)
    for s in ring:
        if s.name == STEP and s.engine is not None:
            steps[s.engine].append(s)
    out = {}
    for eng, mine in steps.items():
        tid = collections.Counter(s.tid for s in mine).most_common(1)[0][0]
        out[eng] = (mine[-1].fields.get("device"),
                    [s for s in ring if s.engine == eng and s.tid == tid
                     and s.name not in NOT_WORK])
    return out


def plain_decode_steps(spans, t0, t1):
    """[(step, its istpu.model.decode)] of the thread's decode steps
    that started in [t0, t1) and hold no admission and no offload, as
    decode_host_p50_ms picks them."""
    steps = {s.id: s for s in spans if s.name == STEP
             and s.fields.get("kind") == "decode" and t0 <= s.t0_ns < t1}
    decode = {}
    for s in spans:
        if s.parent not in steps:
            continue
        if s.name in decode_host_p50_ms.OTHER_WORK:
            decode[s.parent] = None
        elif s.name == "istpu.model.decode":
            decode.setdefault(s.parent, s)
    return [(steps[i], d) for i, d in decode.items() if d is not None]


def lead_and_lag(spans, modules, t0, t1, needles):
    """([ns from a plain decode step's start to the start of its decode
    program on the device], [ns from the end of the LAST program that
    started inside the step's istpu.model.decode to the end of that
    span]) over the plain decode steps of the window; a step whose
    decode span holds no run of a program named by `needles` is left
    out."""
    runs = sorted((s, s + d, n) for n, s, d in modules)
    starts = [r[0] for r in runs]
    lead, lag = [], []
    for step, d in plain_decode_steps(spans, t0, t1):
        inside = runs[bisect.bisect_left(starts, d.t0_ns):
                      bisect.bisect_left(starts, d.t0_ns + d.dur_ns)]
        first = next((r for r in inside
                      if any(n in r[2] for n in needles)), None)
        if first is None:
            continue
        lead.append(first[0] - step.t0_ns)
        lag.append(d.t0_ns + d.dur_ns - max(r[1] for r in inside))
    return lead, lag


def join(plain, ring, needles, closed_ns):
    """The whole reduction, from the plain trace, the ring and the unix
    time the traced window's span was left at (`clock`): {"clock" (with
    "device_skew": {plane: `device_skew`}), "window_s", "idle_s",
    "idle_by" ({name: s}), "planes" ({plane: engine id}), "lead_ns",
    "lag_ns" (of the planes whose skew is known at all),
    "loop_spans" (whether the program records the loop's own spans)};
    "idle_by" and what follows are missing where the clock does not
    hold."""
    out = {"clock": clock(plain, ring, closed_ns)}
    c = out["clock"]
    if c is None or c["pairs"] < MIN_PAIRS \
            or c["quartile_distance_ns"] > MAX_QUARTILE_NS:
        return out
    w0, w1 = trace.window_of(plain)
    by_engine = engines(ring)
    by_device = {dev: eng for eng, (dev, _) in by_engine.items()}
    idle_by = collections.defaultdict(int)
    idle = planes = 0
    c["device_skew"] = {}
    out.update(planes={}, lead_ns=[], lag_ns=[])
    for name, dev in plain["devices"].items():
        # Everything of this plane on the plane's own clock.
        index = device_index(name)
        skew = device_skew(dev.get("runs", ()), plain.get("launches", {}),
                           index or 0)
        c["device_skew"][name] = skew
        by = skew["skew_ns"] if skew else 0
        t0, t1 = w0 - by, w1 - by
        gaps = idle_gaps(dev, t0, t1)
        if gaps is None:
            continue
        planes += 1
        idle += sum(b - a for a, b in gaps)
        eng = by_device.get(index)
        if eng is None and len(by_engine) == len(plain["devices"]) == 1:
            (eng,) = by_engine  # over a mesh a step names no chip
        out["planes"][name] = eng
        spans = [s._replace(t0_ns=s.t0_ns - c["offset_ns"] - by)
                 for s in (by_engine[eng][1] if eng is not None else ())]
        split(gaps, timeline(spans, t0, t1), idle_by)
        if skew:
            lead, lag = lead_and_lag(spans, dev["modules"], t0, t1, needles)
            out["lead_ns"] += lead
            out["lag_ns"] += lag
    n = max(1, planes)
    out.update(window_s=(w1 - w0) / 1e9, idle_s=idle / n / 1e9,
               idle_by={k: v / n / 1e9 for k, v in sorted(
                   idle_by.items(), key=lambda kv: -kv[1]) if v},
               loop_spans=any(s.name in LOOP_SPANS[1:] for s in ring))
    return out


def joined(obs):
    """`join` of this run's trace and ring, read once a run and kept on
    `obs`; None where there is nothing to read or the clock does not
    hold."""
    if obs.trace is None:
        return None
    found = getattr(obs, "idle_by_span", _UNREAD)
    if found is not _UNREAD:
        return found
    found = None
    try:
        t_read = time.perf_counter()
        plain = plain_of_run()
        ring = program_spans.ring(obs) if plain is not None else None
        if ring is not None:
            t_join = time.perf_counter()
            found = join(plain, ring,
                         serve.program_names(obs.conf, "decode"),
                         int(obs.trace_window[1] * 1e9))
            print("clock: " + json.dumps(found["clock"]), flush=True)
            if "idle_by" in found:
                print("idle_by_program_span: " + json.dumps(
                    {k: round(v, 6) for k, v in found["idle_by"].items()}),
                    flush=True)
                print("idle_by_span: " + json.dumps({
                    "idle_s": round(found["idle_s"], 6),
                    "window_s": round(found["window_s"], 6),
                    "planes": found["planes"],
                    "read_s": round(t_join - t_read, 2),
                    "join_s": round(time.perf_counter() - t_join, 2)}),
                    flush=True)
            else:
                found = None
    except Exception as e:  # a reader never fails a run
        print(f"idle by span: nothing read ({type(e).__name__}: {e})",
              flush=True)
        found = None
    obs.idle_by_span = found
    return found


def p50_ms(obs, key):
    """Median in ms of `joined(obs)[key]`, or None."""
    found = joined(obs)
    return None if found is None else program_spans.p50_ms(found[key])


def p95_ms(values_ns):
    q = stats.quantile(list(values_ns), 0.95)
    return None if q is None else q / 1e6
