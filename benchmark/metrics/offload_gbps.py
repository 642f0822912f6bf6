"""Bytes over seconds inside the benchmark's spans around put_kv_pages and
conn.sync (HBM gather, device to host, copy into the pool, commit), over
the window.
"""

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "GB/s"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def read(obs):
    puts = obs.spans_named("put_kv_pages")
    secs = sum(s.seconds for s in puts) + sum(
        s.seconds for s in obs.spans_named("sync"))
    if not puts or not secs:
        return None
    return sum(s.nbytes for s in puts) / 1e9 / secs
