"""Share of the admitted prompt tokens that came from the store:
prefix_hit_pages x page over that plus prefill_tokens, window delta of
ServingEngine.stats.

Moves itl_mean_ms: every admission (probe, restore, prefill) runs on the
one engine thread and stalls all decoding slots. Where ttft_p50_ms is an
end-to-end metric of the cell, it moves that too.
"""

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    page = obs.conf["serving"]["page_size"]
    hit = obs.counters.get("prefix_hit_pages", 0) * page
    total = hit + obs.counters.get("prefill_tokens", 0)
    return 100.0 * hit / total if total else None
