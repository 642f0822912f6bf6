"""Of the time the restore thread spent staging the hits that the
window's admissions took (digests, probe, pin, copy and host-to-device
transfer of a queued request, beside the decode steps), the share the
engine thread did NOT wait for: 100 * (1 - sum(`staged_wait_ns`) /
sum(`staged_ns`)) over the istpu.sched.admit spans that started in the
window, were admitted and had hit pages. The engine writes both on the
admission's span: `staged_ns`, the duration of the request's
istpu.cache.stage span (0 where the admission made the store call
itself), and `staged_wait_ns`, what the engine thread waited for that
staging inside the admission.

100 %: every hit's pages were in HBM when its admission looked. A hit
that arrives at an engine with nothing to step is waited for whole and
pulls the share down by its own length (there is nothing to overlap it
with, and nobody waits but the request itself); its wait also holds
the hand-over between the two threads, which is no part of the staging,
so an admission's wait counts up to its staging's length and the share
stays within 0 and 100. A program whose
admissions carry no such fields gives nothing, and so does a window in
which no hit was staged.

Moves itl_mean_ms: what is not waited for is store time that left the
gap between two tokens of every decoding sequence.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"
SPAN = "istpu.sched.admit"


def value(obs, spans):
    staged = waited = 0
    for s in program_spans.started_in_window(obs, spans, SPAN):
        if s.fields.get("outcome") == "admitted" \
                and s.fields.get("hit_pages", 0) > 0:
            took = s.fields.get("staged_ns", 0)
            staged += took
            waited += min(s.fields.get("staged_wait_ns", 0), took)
    return 100.0 * (1 - waited / staged) if staged else None


def read(obs):
    return program_spans.read(obs, value)
