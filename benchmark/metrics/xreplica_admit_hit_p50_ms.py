"""Median of the program's istpu.sched.admit spans that started in the
window, were admitted and restored pages another replica wrote
(`foreign_pages` > 0): probe, restore, pages_to_kv, pool write and the
prefix prefill of one cross-replica hit, over all replicas. Read
against admit_hit_p50_ms of the one-replica control: the engine runs
the same code for a hit on its own pages, so the difference is what
four engine threads in one process and four clients of one store cost.

A program whose spans carry no `foreign_pages` gives nothing.

Moves itl_mean_ms: an admission runs on its replica's one engine thread,
so every decoding slot of that replica sees it as a gap.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def foreign_admissions(obs, spans):
    """The window's successful admissions with foreign hit pages."""
    return [s for s in program_spans.started_in_window(
        obs, spans, "istpu.sched.admit")
        if s.fields.get("outcome") == "admitted"
        and s.fields.get("foreign_pages", 0) > 0]


def value(obs, spans):
    return program_spans.p50_ms(
        s.dur_ns for s in foreign_admissions(obs, spans))


def read(obs):
    return program_spans.read(obs, value)
