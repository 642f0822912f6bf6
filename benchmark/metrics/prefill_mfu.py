"""FLOPs the prefills of the traced seconds need (prefill_flops of the
configuration's costs module, lib/costs.py unless its file names
another, per admission, from the probe spans' prompt pages and hit; the
top-k experts only for a sparse model, so dense dispatch shows as waste)
over the published bf16 peak and the device time of the prefill
programs.

Moves itl_mean_ms: every admission (probe, restore, prefill) runs on the
one engine thread and stalls all decoding slots. Where ttft_p50_ms is an
end-to-end metric of the cell, it moves that too.
"""

from benchmark.lib import serve, trace

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"


def read(obs):
    if obs.trace is None or obs.peaks is None:
        return None
    t = sum(trace.times_of(obs, "prefill"))
    page = obs.conf["serving"]["page_size"]
    costs = serve.costs_module(obs.conf)
    flops = 0
    for s in obs.spans_named("probe", traced=True):
        # a probe carries one key per page but the last: the prompt is
        # n_keys + 1 pages (every prompt the generator makes is whole pages)
        hit = min(s.result or 0, s.n_keys)
        flops += costs.prefill_flops(
            obs.conf, (s.n_keys + 1 - hit) * page, hit * page)
    if not t or not flops:
        return None
    return 100.0 * flops / obs.peaks["bf16_flops_per_s"] / t
