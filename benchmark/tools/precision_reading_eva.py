#!/usr/bin/env python3
"""Chip tool, run once when evabyte's tolerances are set: the SECOND
readings a limit is set between, as rows of the float32 reference with
one fault planted in its mathematics against the reference itself
(benchmark/reference/evabyte_eva.py FAULTS: what a program with that
fault would compute, in exact arithmetic), at the cell's depths:

  no_summaries     summaries never visible
  summaries_early  a window's summaries visible a window early (every
                   finished chunk, its own window's too)
  fold_120         the fold over 120 of a window's 128 chunks
  no_phi           phi left out (a plain mean of v)
  no_mu            mu left out
  rotated_at_row   the last window's rows rotated at their cache ROW,
                   not their position (the fault a table of rows invites)
  fp8_reference    the reference itself on matrices rounded to
                   float8_e4m3fn, the nearest precision below the
                   configuration's bfloat16

Per fault: the worst |logit difference| over the last `--rows`
positions of the prompt (what `logit_tol` is held against) and the
worst token deficit there (the reference's maximum less its logit of
the token the faulty row would pick: what `token_eps` is held against).
The FIRST readings, the program's own rows and tokens against the
reference, are the cell's `correct:` lines.

    chiprun -- python3 benchmark/tools/precision_reading_eva.py \
        --config evabyte --lengths 13552,26096 \
        --out chiprun_out/precision_eva.jsonl
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="evabyte")
    ap.add_argument("--seeds", default="2147484101")
    ap.add_argument("--lengths", default="13552,26096")
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import serve

    conf = serve.load_config(f"benchmark/configs/{args.config}.json",
                             args.rehearsal)
    model, cfg = serve.model_config(conf)
    ref = serve.reference_module(conf)
    out = None
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        out = open(args.out, "a")

    def say(row):
        line = "precision_reading: " + json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    for seed in (int(s) for s in args.seeds.split(",")):
        params = serve.init_weights(model, cfg, seed)
        low = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            if x.ndim >= 2 else x, params)
        for n in (int(x) for x in args.lengths.split(",")):
            if args.rehearsal:
                n = max(cfg.fold_window + 40, n // 8)
            toks = np.random.default_rng(seed + n).integers(
                0, cfg.vocab_size, n).astype(np.int32)
            at = list(range(n - args.rows, n))
            want = np.asarray(ref.forward(params, conf, toks, at)[0])
            top = np.sort(want, axis=-1)
            row = {"seed": seed, "length": n,
                   "max_abs_logit": float(np.abs(want).max()),
                   "top1_minus_top2": float((top[:, -1] - top[:, -2]).min())}
            for fault in (*ref.FAULTS, "fp8_reference"):
                if fault == "fp8_reference":
                    got = ref.forward(low, conf, toks, at)[0]
                else:
                    got = ref.forward(params, conf, toks, at, fault=fault)[0]
                got = np.asarray(got)
                picked = got.argmax(-1)
                deficit = want.max(-1) - want[np.arange(len(at)), picked]
                row[fault] = {
                    "logit": float(np.abs(got - want).max()),
                    "logit_least_row": float(
                        np.abs(got - want).max(-1).min()),
                    "token": float(deficit.max())}
            say(row)
        del params, low


if __name__ == "__main__":
    sys.exit(main())
