#!/usr/bin/env python3
"""time_paged_decode — the bf16 paged-decode kernel alone, on the chip.

One `paged_flash_decode` call of 16 rows by table length (192, 264,
384, 832) x live share of the 16 x table grid (10 %, 50 %, 100 %) x
how the live pages lie (`even`: every row at that share of its table;
`few`: whole rows until the share is met, the rest inactive rows of
length 1 over entry 0, as an engine with free slots passes them) x the
four operand shapes the repo runs:

  gqa4     8 kv heads x 128, group 4, the whole pool + `layer`
           (mistral7b, mixtral8x7b)
  packed   `kv_pack` rows of 4 x 128 lanes, group 8, the whole pool
           (granite4h-micro)
  group7   4 kv heads x 128, 28 query heads (the group padded 7 -> 8),
           the whole pool; at table 264 with the 4,096 band
           (smallthinker21b)
  sliced   2 kv heads x 64 out of a pool: sliced to its layer, lanes
           and kv heads padded (tests only)

Time: R calls chained inside one jitted loop (each call's query is the
call before's output, so nothing overlaps). `kernel_us` is the device's
own time of the Mosaic call, the median over the R calls of one traced
run (the `tpu_custom_call` events on the device's "XLA Ops" line);
`call_us` is wall time / R, the best of a few untraced repeats, which
also holds what the wrapper adds around the kernel (a layer sliced out
and padded) and about 28 us a call of the loop itself (an empty kernel
reads that). `floor_us` is the live K and V bytes at the HBM's rate.
Prints one JSON line a case; `--out` also writes the lines to a file
(under chiprun_out/ on the chip).

The module under test is the `infinistore_tpu` that `--root` holds
(default: this checkout), so a parent commit unpacked elsewhere is
timed by the same script:

  chiprun -- python3 tools/time_paged_decode.py --out chiprun_out/change.jsonl
  chiprun -- python3 tools/time_paged_decode.py --root build/parent ...
"""

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

TABLES = (192, 264, 384, 832)
SHARES = (0.1, 0.5, 1.0)
ROWS, PAGE, HBM_GBPS = 16, 16, 819.0
# name: (query heads, kv heads, head_dim); K and V are a whole pool
SHAPES = {
    "gqa4": (32, 8, 128),
    "packed": (32, 4, 128),
    "group7": (28, 4, 128),
    "sliced": (8, 2, 64),
}


def lengths(table, share, lie):
    """Tokens a row holds, 16 rows, for a live share of the grid."""
    import numpy as np

    if lie == "even":
        return np.full(ROWS, max(1, round(share * table * PAGE)), np.int32)
    pages = round(share * table * ROWS)
    out = np.ones(ROWS, np.int32)
    for r in range(ROWS):
        take = min(pages, table)
        if take <= 0:
            break
        out[r] = take * PAGE
        pages -= take
    return out


def kernel_times(trace_dir):
    """Durations (ns) of the Mosaic calls on the first device plane of
    the trace under `trace_dir`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                return [ev.duration_ns for ev in line.events
                        if "tpu_custom_call" in ev.name]
    return []


def operands(shape, table):
    """q, a two-layer K and V pool that holds 16 whole tables and the
    scratch page 0, and a table of distinct pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    n_heads, n_kv, hd = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(table), 3)
    pool = (2, ROWS * table + 1, PAGE, n_kv, hd)
    k = jax.random.normal(ks[0], pool, jnp.bfloat16)
    v = jax.random.normal(ks[1], pool, jnp.bfloat16)
    q = jax.random.normal(ks[2], (ROWS, n_heads, hd), jnp.bfloat16)
    pt = 1 + np.random.default_rng(table).permutation(ROWS * table)
    return q, k, v, pt.reshape(ROWS, table).astype(np.int32)


def time_case(paged, shape, table, share, lie, ops, reps, rounds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    q, k, v, pt = ops
    n_kv, hd = k.shape[-2:]
    window = 4096 if (shape == "group7" and table == 264) else 0
    lens = lengths(table, share, lie)
    pt = pt.copy()
    pt[lens == 1, 0] = 0  # an inactive row: length 1 over entry 0
    pt, sl = jnp.asarray(pt), jnp.asarray(lens)

    @jax.jit
    def chain(q, k, v, pt, sl):
        def one(_, q):
            return paged.paged_flash_decode(q, k, v, pt, sl, window=window,
                                            layer=1)
        return jax.lax.fori_loop(0, reps, one, q)

    chain(q, k, v, pt, sl).block_until_ready()
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        chain(q, k, v, pt, sl).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir)
        chain(q, k, v, pt, sl).block_until_ready()
        jax.profiler.stop_trace()
        kernel = kernel_times(tdir)
    live = np.minimum(lens, window) if window else lens
    nbytes = int(np.sum(live)) * 2 * n_kv * hd * 2
    return {
        "shape": shape, "table": table, "share": share, "lie": lie,
        "window": window, "live_pages": int(np.sum(-(-live // PAGE))),
        "grid_pages": ROWS * table,
        "kernel_us": round(statistics.median(kernel) / 1e3, 2)
        if kernel else None, "kernel_calls": len(kernel),
        "call_us": round(best / reps * 1e6, 2),
        "floor_us": round(nbytes / HBM_GBPS / 1e3, 2),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tables", default=",".join(map(str, TABLES)))
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    import jax

    from infinistore_tpu.ops import pallas_paged_attention as paged

    if jax.default_backend() != "tpu":
        sys.exit("time_paged_decode: no TPU; a CPU time is not a kernel time")
    lines = []
    for shape in args.shapes.split(","):
        for table in map(int, args.tables.split(",")):
            ops = operands(shape, table)
            for share in SHARES:
                for lie in ("even", "few"):
                    row = time_case(paged, shape, table, share, lie, ops,
                                    args.reps, args.rounds)
                    row["device"] = jax.devices()[0].device_kind
                    lines.append(row)
                    print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)


if __name__ == "__main__":
    main()
