"""Bytes over seconds inside the benchmark's spans around get_kv_pages
(store pool to HBM), over the window.

Moves itl_mean_ms: every admission (probe, restore, prefill) runs on the
one engine thread and stalls all decoding slots. Where ttft_p50_ms is an
end-to-end metric of the cell, it moves that too.
"""

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "GB/s"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def read(obs):
    spans = obs.spans_named("get_kv_pages")
    secs = sum(s.seconds for s in spans)
    return sum(s.nbytes for s in spans) / 1e9 / secs if secs else None
