"""Percentile arithmetic and the spread the bounds are set from."""

import math
import statistics


def quantile(values, q):
    """Nearest-rank quantile: the smallest value with at least q of the
    samples at or below it. None for no samples."""
    if not values:
        return None
    v = sorted(values)
    k = max(1, math.ceil(q * len(v)))
    return v[k - 1]


def samples_beyond(n, q):
    """How many of n samples lie beyond the q-quantile."""
    return n - max(1, math.ceil(q * n)) if n else 0


def supported(n, q, beyond=10):
    """The rule of the choosing-metrics guide: a percentile is reported
    with at least ten samples beyond it."""
    return samples_beyond(n, q) >= beyond


def highest_supported_percentile(n, beyond=10):
    """The highest whole percentile with `beyond` samples beyond it, or
    None if even the median has not."""
    for p in range(99, 49, -1):
        if supported(n, p / 100.0, beyond):
            return p
    return None


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, by statistics.quantiles(values, n=4), as the driver reads
    it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def hist_percentile_us(hist, q):
    """Percentile of the store's log2 latency histogram (bucket b holds
    [2^b, 2^(b+1)) us; native/src/trace.h), bucket midpoint, as the
    server's own p50/p99 do. None for an empty histogram."""
    total = sum(hist)
    if total <= 0:
        return None
    need = q * total
    seen = 0
    for b, c in enumerate(hist):
        seen += c
        if seen >= need and c:
            return 1.5 * (1 << b) if b else 1.0
    return 1.5 * (1 << (len(hist) - 1))
