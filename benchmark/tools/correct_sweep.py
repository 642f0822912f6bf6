#!/usr/bin/env python3
"""Chip tool: runs the correctness check ALONE over many seeds in one
process (fresh weights, engines and store per seed; compiled programs
are shared) and writes every statistic it decides on - token deficits,
first-token logit differences, router margins - so that the tolerances
in benchmark/reference/tolerances.json are set from a distribution and
not from the seeds a builder happened to try.

    python3 benchmark/tools/correct_sweep.py --workload \
        mixtral8x7b-sessions --seeds 24 --out chiprun_out/correct.jsonl
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print("correct_sweep: needs a TPU", file=sys.stderr)
        return 2
    from benchmark.lib import manifest
    from benchmark.lib.cell import Cell

    bench = manifest.load()
    cell = manifest.cell_of(bench, args.workload)
    entry = manifest.config_of(bench, cell["config"])
    for i in range(args.seeds):
        seed = args.first_seed + i * 7919
        c = Cell(cell, entry, seed, log=lambda m: print(m, flush=True))
        try:
            c.setup()
            ok = c.warm_and_check()
            row = {"workload": args.workload, "seed": seed, "ok": ok,
                   **c.details}
        finally:
            c.close()
            c = None
            gc.collect()  # the next seed needs this one's HBM back
        line = json.dumps(row)
        print("correct_sweep: " + json.dumps(
            {k: v for k, v in row.items() if k != "per_turn"}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
