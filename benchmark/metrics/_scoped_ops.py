"""Device time of the operations that came from one `jax.named_scope`,
inside the step programs of one kind. benchmark/lib/trace.py reduces a
trace to HLO instruction names, which do not say what stage of the
model an operation belongs to. The profile does: its metadata plane
holds every traced program's HLO proto, and each instruction's
metadata there carries the `op_name` it was traced under (e.g.
"jit(_decode_fused_st)/.../ssm.step/mul"; a fusion carries one of its
instructions', a Pallas kernel the scope it was called under). This
module reads the run's .xplane.pb once more for that and nothing else,
without touching lib/trace.py.

The trace lies in the cell's run directory (lib/cell.py: a
`bench_run_*` directory under the temporary directory, with `trace/`
inside), which still exists when the readers run. The protobuf classes
are the ones the installed profiler plugin's TensorFlow brings.

Where there is no trace, no such scope in it (a program without these
operations, as a parent commit is), or the classes cannot be imported,
`seconds` returns None and raises nothing.
"""

import bisect
import glob
import os
import tempfile

from benchmark.lib import trace

_cache = {}


def _xplane():
    runs = sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                         "bench_run_*", "trace")),
                  key=os.path.getmtime)
    return trace.find_xplane(runs[-1]) if runs else None


def op_names(space):
    """{program (module) name: {instruction name: op_name}} from the
    HLO protos in the profile's metadata plane."""
    from tensorflow.compiler.xla.service import hlo_pb2

    out = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        for md in plane.event_metadata.values():
            for st in md.stats:
                if not st.bytes_value:
                    continue
                proto = hlo_pb2.HloProto()
                proto.ParseFromString(st.bytes_value)
                names = out.setdefault(md.name, {})
                for comp in proto.hlo_module.computations:
                    for ins in comp.instructions:
                        if ins.metadata.op_name:
                            names[ins.name] = ins.metadata.op_name
    return out


def scoped_events(path):
    """[(op_name + instruction name, start_ns, dur_ns)] of every device
    operation, and [(module name, start_ns, dur_ns)] of every program
    run, over all TensorCore planes of the trace at `path`."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    by_module = op_names(space)
    ops, modules = [], []
    for plane in space.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name
                and "SparseCore" not in plane.name):
            continue
        meta = plane.event_metadata
        lines = {ln.name: ln for ln in plane.lines}
        runs, starts = [], []
        for ln_name, into in ((trace.MODULES_LINE, runs),
                              (trace.OPS_LINE, None)):
            ln = lines.get(ln_name)
            if ln is None:
                continue
            t0 = ln.timestamp_ns
            for ev in ln.events:
                name = meta[ev.metadata_id].name
                start = t0 + ev.offset_ps // 1000
                dur = ev.duration_ps // 1000
                if into is not None:
                    into.append((name, start, dur))
                    continue
                # the program run this operation lies in
                i = bisect.bisect_right(starts, start) - 1
                inside = runs[i][0] if i >= 0 and start < sum(
                    runs[i][1:]) else ""
                scope = by_module.get(inside, {}).get(trace.short(name), "")
                ops.append((scope + " " + trace.short(name), start, dur))
            if into is not None:
                runs.sort(key=lambda r: r[1])
                starts = [r[1] for r in runs]
        modules += runs
    return ops, modules


def seconds_in(ops, modules, window, needles, scopes):
    """(seconds the operations whose scope path contains any of
    `scopes` ran, program runs, seconds those runs took): inside runs
    of the programs whose name contains any of `needles`, everything
    clipped to `window` (t0_ns, t1_ns)."""
    t0, t1 = window
    runs = sorted((s, s + d) for name, s, d in modules
                  if any(n in name for n in needles) and s + d > t0
                  and s < t1)
    starts = [r[0] for r in runs]
    total = 0
    for where, s, d in ops:
        if not any(sc in where for sc in scopes):
            continue
        a, b = max(s, t0), min(s + d, t1)
        i = bisect.bisect_right(starts, s) - 1
        if b > a and i >= 0 and s < runs[i][1]:
            total += b - a
    whole = sum(min(b, t1) - max(a, t0) for a, b in runs)
    return total / 1e9, len(runs), whole / 1e9


def seconds(obs, kind, scopes):
    """`seconds_in` of the `scopes` operations inside the
    configuration's `kind` ("decode" | "prefill") programs in the
    traced window, or None where no such operation ran."""
    from benchmark.lib import serve

    if obs.trace is None:
        return None
    try:
        path = _xplane()
        if path is None:
            return None
        if path not in _cache:
            _cache.clear()
            plain = trace.read_xplane(path)
            _cache[path] = scoped_events(path) + (trace.window_of(plain),)
        ops, modules, window = _cache[path]
        found = seconds_in(ops, modules, window,
                            serve.program_names(obs.conf, kind), scopes)
    except Exception as e:  # a reader never fails a run
        print(f"scoped ops: nothing read ({type(e).__name__}: {e})",
              flush=True)
        return None
    return found if found[0] > 0 and found[1] else None
