"""The program's own spans (infinistore_tpu/utils/profiling.py: one
ring per process, always on), as the metric readers take them: read in
the benchmark's process, which is the engine's, after the window.

A program without the recorder (a parent commit measured with this
benchmark) gives None, and so does a ring that no longer reaches back
to the window's start; both say so on stdout and raise nothing, and the
result line then leaves the metric out.
"""

from . import stats


def ring(obs, spans=None):
    """Every span in the program's ring, oldest first, or None. `spans`
    stands in for the ring in tests."""
    if spans is None:
        try:
            from infinistore_tpu.utils import profiling

            spans = profiling.spans()
        except (ImportError, AttributeError) as e:
            print(f"program spans: this program records none "
                  f"({type(e).__name__}: {e})", flush=True)
            return None
    w0_ns = obs.window[0] * 1e9
    # Records enter the ring as they END, so whatever the ring has
    # dropped ended before its first record did: if that one ended
    # before the window began, everything that started inside is here.
    if not spans or spans[0].t0_ns + spans[0].dur_ns > w0_ns:
        print(f"program spans: the ring of {len(spans)} records does not "
              f"reach back to the window's start", flush=True)
        return None
    return spans


def started_in_window(obs, spans, name):
    """The spans called `name` that started inside obs.window."""
    w0_ns, w1_ns = obs.window[0] * 1e9, obs.window[1] * 1e9
    return [s for s in spans if s.name == name and w0_ns <= s.t0_ns < w1_ns]


def admitted_ns(obs, spans, hit):
    """Durations (ns) of the window's successful admissions with
    (`hit`) or without hit pages."""
    return [s.dur_ns for s in started_in_window(obs, spans,
                                                "istpu.sched.admit")
            if s.fields.get("outcome") == "admitted"
            and (s.fields.get("hit_pages", 0) > 0) == hit]


def read(obs, value):
    """What a metric's read(obs) returns: value(obs, ring), or None
    where there is no ring to read."""
    spans = ring(obs)
    return None if spans is None else value(obs, spans)


def p50_ms(durations_ns):
    """Median (nearest rank, as every p50 here) in ms; None for none."""
    q = stats.quantile(list(durations_ns), 0.50)
    return None if q is None else q / 1e6
