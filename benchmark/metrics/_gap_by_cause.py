"""The mean gap between two tokens of one request, split by what the
engine thread did in it: ServingEngine.stats' `gap_*` counters, which
`_emit` advances where the tokens are emitted (docs/serving.md, "The
gap between tokens"), as deltas over the WHOLE window, summed over the
replicas. `gap_tokens` gaps lasted `gap_ns` on the engine's clock; of
those ns the engine thread spent `gap_ns_<cause>` under a span of that
cause (only the outermost listed span counts, so the causes never
overlap), and what no cause covers is `other`: the loop around the
steps. So, in ms a token,

    gap_engine_mean_ms = gap_step_ms + gap_admit_miss_ms
        + gap_admit_hit_ms + gap_admit_piece_ms + gap_offload_ms
        + gap_other_ms

A program without the counters (a parent commit) gives None for every
one of them, and the result line leaves them out.
"""

CAUSES = ("step", "admit_miss", "admit_hit", "admit_piece", "offload")


def per_gap(obs, key):
    """The counter `key` a gap: None without a gap in the window, 0.0
    for a counter that never moved."""
    gaps = obs.counters.get("gap_tokens", 0)
    if not gaps:
        return None
    return obs.counters.get(key, 0) / gaps


def ms_per_token(obs, key):
    """`key`'s ns a gap, in ms (None and 0.0 as `per_gap`)."""
    ns = per_gap(obs, key)
    return None if ns is None else ns / 1e6


def other_ms(obs):
    """What the five causes leave of the mean gap."""
    total = ms_per_token(obs, "gap_ns")
    if total is None:
        return None
    return total - sum(ms_per_token(obs, f"gap_ns_{c}") for c in CAUSES)
