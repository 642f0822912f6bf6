"""Share of the admitted prompt tokens that came from pages ANOTHER
replica wrote: foreign_hit_pages x page over prefix_hit_pages x page
plus prefill_tokens, window delta of ServingEngine.stats summed over
the replicas. With rotated routing every hit is such a page, so this
reads what prefix_hit_share reads in the one-replica control; lower
means a turn outran the visibility of its predecessor's offload, or the
pool evicted a live session.

A program without the counter (a parent commit) gives nothing.

Moves itl_mean_ms: every admission (probe, restore, prefill) runs on its
replica's one engine thread and stalls that replica's decoding slots.
"""

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    if "foreign_hit_pages" not in obs.counters:
        return None
    page = obs.conf["serving"]["page_size"]
    total = obs.counters.get("prefix_hit_pages", 0) * page \
        + obs.counters.get("prefill_tokens", 0)
    foreign = obs.counters["foreign_hit_pages"] * page
    return 100.0 * foreign / total if total else None
