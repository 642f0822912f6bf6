"""Phase-shifting workload scenario (ISSUE 17 satellite), and the two
test oracles it is built on (a seeded Zipfian trace, an exact LRU
simulator).

One deterministic op sequence for the iosched tests, modeling the
traffic shape the background-IO scheduler exists for:

  1. ``bulk_load``   — every key written once in insertion order: the
     pool overfills past reclaim_high, so the spill/reclaim machinery
     is saturated when phase 2 starts.
  2. ``interactive`` — a Zipfian read trace (zipf_trace, the seeded
     generator the workload-observability tests replay too): hot-key
     gets that demand-promote against the spill backlog. This is the
     phase whose p99 the scheduler protects.
  3. ``scan``        — one sequential sweep over the whole key space:
     a cold scan that floods prefetch/promote with low-value work and
     hands the closed-loop controller something to throttle.

The sequence is a pure function of (nkeys, interactive_len, alpha,
seed), so two servers replaying it see byte-identical traffic — the
A/B arms of the deterministic starvation test replay EXACTLY the same
ops.
"""

import time

PHASES = ("bulk_load", "interactive", "scan")


def zipf_trace(nkeys, length, alpha=0.9, seed=1234):
    """Deterministic Zipfian reference trace: key INDICES drawn from a
    rank-frequency power law (rank r with weight r^-alpha) by a seeded
    generator, with the rank->key mapping shuffled by the same seed so
    popularity is not correlated with insertion order. The scenario
    below and the workload tests' exact stack-distance simulator replay
    EXACTLY this sequence."""
    import numpy as np

    rng = np.random.default_rng(seed)
    weights = 1.0 / np.arange(1, nkeys + 1, dtype=np.float64) ** alpha
    weights /= weights.sum()
    ranks = rng.choice(nkeys, size=length, p=weights)
    perm = rng.permutation(nkeys)
    return [int(perm[r]) for r in ranks]


def exact_lru_miss_ratio(trace, capacity_keys):
    """Exact stack-distance (LRU) simulation over a key-index trace at
    a fixed capacity in KEYS (uniform object size): the oracle the
    sampler's predicted miss ratio is pinned against."""
    from collections import OrderedDict

    lru = OrderedDict()
    misses = 0
    for k in trace:
        if k in lru:
            lru.move_to_end(k)
        else:
            misses += 1
            if len(lru) >= capacity_keys:
                lru.popitem(last=False)
            lru[k] = True
    return misses / len(trace) if trace else 0.0


def build_scenario(nkeys, interactive_len=None, alpha=0.9, seed=4242):
    """Return the full op list: ``(phase, op, key_index)`` triples
    where op is "put" (bulk_load) or "get" (interactive, scan)."""
    if interactive_len is None:
        interactive_len = 4 * nkeys
    ops = [("bulk_load", "put", i) for i in range(nkeys)]
    trace = zipf_trace(nkeys, interactive_len, alpha=alpha, seed=seed)
    ops.extend(("interactive", "get", k) for k in trace)
    ops.extend(("scan", "get", i) for i in range(nkeys))
    return ops


def run_scenario(ops, put_fn, get_fn, clock=time.perf_counter):
    """Replay the op list, timing every op. put_fn/get_fn take a key
    INDEX (the caller owns key naming and payloads). Returns
    ``{phase: [latency_seconds, ...]}`` in op order — callers take
    p50/p99 per phase or sum for throughput."""
    lats = {p: [] for p in PHASES}
    for phase, op, idx in ops:
        fn = put_fn if op == "put" else get_fn
        t0 = clock()
        fn(idx)
        lats[phase].append(clock() - t0)
    return lats


def phase_percentile(lats, phase, pct):
    """Percentile (in MICROSECONDS) of one phase's latencies, nearest-
    rank — no numpy dependency so tests can call it on tiny lists."""
    xs = sorted(lats.get(phase, []))
    if not xs:
        return 0.0
    k = min(len(xs) - 1, max(0, int(round(pct / 100.0 * len(xs))) - 1))
    return xs[k] * 1e6
