#!/usr/bin/env python3
"""Chip tool, run once when a configuration's tolerance is set: the
SECOND reading a limit is set from. First-token logit rows of one
prompt against the float32 reference when (a) a hit continues from a
state snapshot rounded to bfloat16 where float32 is stated, (b) the
reference itself runs on matrices rounded to float8_e4m3fn, the nearest
precision below the configuration's bfloat16. For configurations of the
hybrid family (a state to round); the numbers went into
benchmark/reference/tolerances_granite_hybrid.json.

    python3 benchmark/tools/precision_reading.py --config granite4h-micro
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
    ap.add_argument("--prefix", type=int, default=2048)
    ap.add_argument("--suffix", type=int, default=112)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import serve

    conf = serve.load_config(f"benchmark/configs/{args.config}.json")
    model, cfg = serve.model_config(conf)
    ref = serve.reference_module(conf)
    n, last = args.prefix, args.prefix + args.suffix - 1
    pre = jax.jit(lambda p, t: model.prefill(p, cfg, t))
    hit = jax.jit(lambda p, t, kvs, st: model.prefill_with_prefix(
        p, cfg, t, kvs, state=st)[0][0, -1])

    def rounded(h):
        return h.astype(jnp.bfloat16).astype(jnp.float32)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = serve.init_weights(model, cfg, seed)
        toks = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, last + 1).astype(np.int32)
        want = np.asarray(ref.forward(params, conf, toks, [last])[0][0])
        _, kvs, states = pre(params, jnp.asarray(toks[None, :n]))
        row = {"seed": seed}
        for name, cast in (("f32_state", lambda h: h),
                           ("bf16_state", rounded)):
            st = [(cast(s["h"]), cast(s["conv"])) for s in states]
            got = np.asarray(hit(params, jnp.asarray(toks[None, n:]), kvs,
                                 st))
            row[name] = float(np.abs(got - want).max())
        low = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            if x.ndim >= 2 else x, params)
        got = np.asarray(ref.forward(low, conf, toks, [last])[0][0])
        row["fp8_weights_reference"] = float(np.abs(got - want).max())
        row["max_abs_logit"] = float(np.abs(want).max())
        top = np.sort(want)[-2:]
        row["top1_minus_top2"] = float(top[1] - top[0])
        print("precision_reading: " + json.dumps(row), flush=True)
        del params, low


if __name__ == "__main__":
    sys.exit(main())
