#!/usr/bin/env python3
"""Chip tool, run once when a traffic file is written: offers one cell's
traffic at several fixed rates in ONE process (one set-up) and prints,
per rate, the tails and whether the backlog grew, so that the knee (the
highest rate with no growing backlog) can be read off and 0.8 x or
1.5 x of it written into the traffic file as a number.

    python3 benchmark/tools/sweep.py --workload mistral7b-sessions \
        --rates 1.0,1.4,1.8 --seconds 20 [--out chiprun_out/sweep.jsonl]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from benchmark.lib import manifest, stats
    from benchmark.lib.cell import Cell

    bench = manifest.load()
    cell = manifest.cell_of(bench, args.workload)
    c = Cell(cell, manifest.config_of(bench, cell["config"]), args.seed,
             log=lambda m: print(m, flush=True))
    try:
        c.setup()
        c.warm_and_check(direct=False)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            # Another seed per rate: the same sessions again would hit
            # what the previous rate left in the store.
            c.seed = args.seed + 1 + i
            obs = c.measure(args.seconds, rate=rate)
            w0, w1 = obs.window
            mid = (w0 + w1) / 2
            first = obs.ttfts_ms(lambda r: r["due"] < mid)
            second = obs.ttfts_ms(lambda r: r["due"] >= mid)
            queued = [len(r.engine.queue) for r in c.replicas]
            due = obs.due_in_window()
            row = {
                "workload": args.workload, "session_rate_per_s": rate,
                "request_rate_per_s": round(len(due) / args.seconds, 2),
                "attempted": len(due),
                "failed": sum(1 for r in due if obs.failed(r)),
                "unfinished": sum(1 for r in due if not r["ended"]),
                "ttft_p50_ms": stats.quantile(obs.ttfts_ms(), 0.5),
                "ttft_p95_ms": stats.quantile(obs.ttfts_ms(), 0.95),
                "ttft_p50_first_half_ms": stats.quantile(first, 0.5),
                "ttft_p50_second_half_ms": stats.quantile(second, 0.5),
                "itl_p50_ms": stats.quantile(obs.gaps_ms(), 0.5),
                "itl_p95_ms": stats.quantile(obs.gaps_ms(), 0.95),
                "tokens_per_s": obs.tokens_in_window() / args.seconds,
                "queued_at_end": queued,
                "compilations_in_window": c.compiled_in_window,
                "counters": {k: obs.counters.get(k) for k in (
                    "prefix_hit_pages", "prefill_tokens", "decode_steps",
                    "decoded_tokens", "offloaded_pages", "preemptions",
                    "restore_misses", "store_errors")},
                "evictions": obs.store_delta.get("evictions"),
            }
            line = json.dumps(row)
            print("sweep: " + line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")
            # Let the backlog drain before the next rate.
            t_end = time.time() + 120
            while time.time() < t_end and any(
                    r.engine.queue or any(s is not None
                                          for s in r.engine.slots)
                    for r in c.replicas):
                time.sleep(0.5)
    finally:
        c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
