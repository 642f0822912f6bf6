#!/usr/bin/env python3
"""Chip tool, run once when a sparse configuration's tolerance is set:
the SECOND reading a limit is set from (benchmark/tools/
precision_reading.py is the hybrid family's). Rows of logits of one
prompt from the float32 reference against the reference itself run on
matrices rounded to float8_e4m3fn, the nearest precision below the
configuration's bfloat16, at positions whose router choice is no
near-tie in EITHER run (`--margin`), so that what is read is the
precision and not a flipped expert.

    python3 benchmark/tools/precision_reading_moe.py --config \
        smallthinker21b --length 6400
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="2147484101,2147494111")
    ap.add_argument("--length", type=int, default=6400)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--margin", type=float, default=0.02)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import serve

    conf = serve.load_config(f"benchmark/configs/{args.config}.json",
                             args.rehearsal)
    model, cfg = serve.model_config(conf)
    ref = serve.reference_module(conf)
    n = args.length
    positions = list(range(n - args.rows, n))

    def to_f8(w):
        if w.ndim < 2:
            return w
        # float8_e4m3fn's 4 exponent and 3 mantissa bits, by the op
        # made for it: a pair of converts is folded away under jit
        return jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = serve.init_weights(model, cfg, seed)
        toks = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, n).astype(np.int32)
        want, m0 = ref.forward(params, conf, toks, positions)
        want, m0 = np.asarray(want), np.asarray(m0)
        # rounded where they lie: the chip does not hold the weights twice
        params = jax.jit(lambda p: jax.tree_util.tree_map(to_f8, p),
                         donate_argnums=0)(params)
        got, m1 = ref.forward(params, conf, toks, positions)
        want, got = np.asarray(want), np.asarray(got)
        clear = (np.asarray(m0).min(axis=1) >= args.margin) \
            & (np.asarray(m1).min(axis=1) >= args.margin)
        diff = np.abs(got - want).max(axis=1)
        top = np.sort(want, axis=1)
        print(json.dumps({
            "seed": seed, "length": n, "rows": len(positions),
            "clear_rows": int(clear.sum()),
            "f8_max_logit_diff_clear": [round(float(d), 4)
                                        for d in diff[clear]],
            "f8_max_logit_diff_all": round(float(diff.max()), 4),
            "max_abs_logit": round(float(np.abs(want).max()), 3),
            "top2_gap_median": round(float(np.median(
                top[:, -1] - top[:, -2])), 4),
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
