"""From a profiler trace to numbers. Two stages, so that the reduction
can be checked against a small recorded trace without a chip:

  read_xplane(path)  the profiler's .xplane.pb -> plain event lists
                     (needs only jax.profiler.ProfileData)
  reduce(events)     plain event lists -> busy seconds, window, top
                     device operations, idle gaps by host span, and the
                     device time of each step program

Plain form (also the recorded trace's JSON):
  {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                       "modules": [[name, start_ns, dur_ns], ...]}},
   "host": [[name, start_ns, dur_ns], ...]}     # the benchmark's spans

Device and host events share the profiler's clock. The traced window is
the host span WINDOW_SPAN, which run.py opens around the traced
seconds.
"""

import glob
import json
import os

from . import serve

WINDOW_SPAN = "bench.trace_window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    return paths[-1] if paths else None


def short(name):
    """An XLA op event is named by its whole HLO line; keep the
    instruction's own name."""
    return name.split(" = ")[0].lstrip("%")[:80]


def read_xplane(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            dev = {"ops": [], "modules": []}
            for ln in lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(ln.name)
                if key is None:
                    continue
                for ev in ln.events:
                    dev[key].append([short(ev.name), int(ev.start_ns),
                                     int(ev.duration_ns)])
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns),
                                            int(ev.duration_ns)])
    return out


def load_recorded(path):
    with open(path) as f:
        return json.load(f)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, t0, t1):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def window_of(events):
    """[t0, t1) of the traced window: the WINDOW_SPAN host span, or
    (no such span) the extent of all device events."""
    for name, s, d in events["host"]:
        if name == WINDOW_SPAN:
            return s, s + d
    starts, ends = [], []
    for dev in events["devices"].values():
        for _, s, d in dev["ops"] + dev["modules"]:
            starts.append(s)
            ends.append(s + d)
    return (min(starts), max(ends)) if starts else (0, 0)


def _gap_owner(gap, host):
    """The benchmark's host span that covers most of an idle gap; among
    spans covering over half of it the shortest (innermost) wins."""
    g0, g1 = gap
    best, best_ov, inner = None, 0, None
    for name, s, e in host:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        if ov > 0.5 * (g1 - g0) and (inner is None
                                     or e - s < inner[1]):
            inner = (name, e - s)
        if ov > best_ov:
            best, best_ov = name, ov
    if inner is not None:
        return inner[0]
    return best if best is not None else "outside_any_bench_span"


def reduce(events, top=10):
    """busy_s and window_s (busy averaged over the device planes that
    ran anything), breakdown lists, and per-program device times."""
    t0, t1 = window_of(events)
    window_s = (t1 - t0) / 1e9
    host = [(n, a, b) for n, a, b in _clip(events["host"], t0, t1)
            if n != WINDOW_SPAN]
    busy, op_time, gaps_by, programs = [], {}, {}, {}
    for dev in events["devices"].values():
        ops = list(_clip(dev["ops"] or dev["modules"], t0, t1))
        if not ops:
            continue
        merged = _union((a, b) for _, a, b in ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, a, b in ops:
            op_time[name] = op_time.get(name, 0.0) + (b - a) / 1e9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                owner = _gap_owner((g0, g1), host)
                gaps_by[owner] = gaps_by.get(owner, 0.0) + (g1 - g0) / 1e9
        for name, a, b in _clip(dev["modules"], t0, t1):
            programs.setdefault(name, []).append((b - a) / 1e9)
    n_dev = max(1, len(busy))

    def ranked(d):
        return [[k, v / n_dev] for k, v in sorted(
            d.items(), key=lambda kv: kv[1], reverse=True)[:top]]

    return {
        "busy_s": sum(busy) / n_dev if busy else 0.0,
        "window_s": window_s,
        "devices": len(busy),
        "device_ops": ranked(op_time),
        "idle_gaps": ranked(gaps_by),
        "programs": programs,
    }


def program_times(reduced, *needles):
    """Device seconds of every run of the step programs whose module
    name contains any of `needles` (e.g. "decode_fused")."""
    out = []
    for name, durs in reduced["programs"].items():
        if any(n in name for n in needles):
            out += durs
    return out


def times_of(obs, kind):
    """program_times of the configuration's "decode" or "prefill"
    programs, by the names its file gives (lib/serve.program_names)."""
    return program_times(obs.trace, *serve.program_names(obs.conf, kind))
