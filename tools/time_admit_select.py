#!/usr/bin/env python3
"""time_admit_select — ONE block of 64 queries of ONE layer of an
admission under a learned selection over K and V rows
(ops/sparse_select.py), alone on the chip at keye-vl2-30b-a3b's shapes:
16 index heads over keys of 128 lanes (64 of them zero), `topk` 2,048,
32 query heads over 4 kv heads of 128, S contiguous rows of prefix +
suffix. The two forms the attention under the selection can take
(PERF.md, PR 49), and the stages both share:

  index     the block's index scores [64, S]
  topk      `select` over them (`jax.lax.top_k`)
  masked    (ii) `taken_mask` from the selection and `attend_masked`
            over ALL S rows under it: no gather, 17 x the FLOPs at 35k
  gathered  (i) the selected K and V rows gathered (2 x 64 x 2,048 rows
            of 1 KB) and `attend_grouped` over them
  block_*   the whole block by each form (index + topk + attention), as
            `select_attend_seq` runs (ii)

A piece of 4,096 tokens is 64 such blocks in each of 5 layers. The form
NOT kept in the tree, (i), is composed here from the pieces the decode
step uses. Time as tools/time_select_decode.py takes it: R calls
chained inside one jitted loop on the first operand, wall time / R, the
best of a few repeats. One JSON line a case; `--out` also writes them
to a file (under chiprun_out/ on the chip).

  chiprun -- python3 tools/time_admit_select.py --out chiprun_out/admit_select.jsonl
"""

import argparse
import json
import os
import sys
import time

BLOCK, TOPK = 64, 2048
INDEX_HEADS, INDEX_WIDTH, HEADS, KV_HEADS, HEAD_DIM = 16, 128, 32, 4, 128
ROWS = (4096, 16384, 32768, 35072)


def operands(s):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf = jnp.bfloat16
    return {
        "qi": jax.random.normal(ks[0], (BLOCK, INDEX_HEADS, INDEX_WIDTH), bf),
        "w": jax.random.normal(ks[1], (BLOCK, INDEX_HEADS), jnp.float32),
        "keys": jax.random.normal(ks[2], (s, INDEX_WIDTH), bf),
        "qa": jax.random.normal(ks[3], (BLOCK, HEADS, HEAD_DIM), bf),
        "k": jax.random.normal(ks[4], (s, KV_HEADS, HEAD_DIM), bf),
        "v": jax.random.normal(ks[5], (s, KV_HEADS, HEAD_DIM), bf),
        "pos": s - BLOCK + jnp.arange(BLOCK, dtype=jnp.int32),
    }


def stages(ss):
    import jax.numpy as jnp

    scale = HEAD_DIM ** -0.5

    def index(qi, w, keys):
        return ss._scores(qi, w, keys, "qhd,sd->qhs")

    def topk(scores, pos):
        return ss.select(scores, pos + 1, TOPK)[0]

    def masked(qa, scores, pos, k, v):
        sel = ss.select(scores, pos + 1, TOPK, with_scores=True)
        return ss.attend_masked(qa, k, v, ss.taken_mask(scores, pos + 1, sel),
                                scale)

    def mask_only(qa, scores, pos, k, v):
        # the mask given, the attention alone (the sort is `topk`'s)
        return ss.attend_masked(qa, k, v, scores > 0, scale)

    def gathered(qa, idx, k, v):
        taken = jnp.ones(idx.shape, bool)
        return ss.attend_grouped(qa, jnp.take(k, idx, axis=0),
                                 jnp.take(v, idx, axis=0), taken, scale)

    def block_masked(qi, w, pos, qa, keys, k, v):
        scores = index(qi, w, keys)
        return masked(qa, scores, pos, k, v)

    def block_gathered(qi, w, pos, qa, keys, k, v):
        idx, taken = ss.select(index(qi, w, keys), pos + 1, TOPK)
        return ss.attend_grouped(qa, jnp.take(k, idx, axis=0),
                                 jnp.take(v, idx, axis=0), taken, scale)

    return {
        "index": (index, ("qi", "w", "keys")),
        "topk": (topk, ("scores", "pos")),
        "attend_all_rows": (mask_only, ("qa", "scores", "pos", "k", "v")),
        "masked": (masked, ("qa", "scores", "pos", "k", "v")),
        "gathered": (gathered, ("qa", "idx", "k", "v")),
        "block_masked": (block_masked,
                         ("qi", "w", "pos", "qa", "keys", "k", "v")),
        "block_gathered": (block_gathered,
                           ("qi", "w", "pos", "qa", "keys", "k", "v")),
    }


def time_chain(fn, arrays, reps, rounds):
    """us a call of `fn(*arrays)`, `reps` calls chained on the first
    array (every integer operand hangs on the call before too)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(first, rest):
        def one(_, carry):
            x, zero = carry
            out = fn(x, *(a + zero if a.dtype == jnp.int32 else a
                          for a in rest))
            s = jnp.sum(out.astype(jnp.float32))
            return (x + (s * 1e-30).astype(x.dtype),
                    (s > 3e38).astype(jnp.int32))
        return jax.lax.fori_loop(0, reps, one, (first, jnp.int32(0)))[0]

    first, rest = arrays[0], tuple(arrays[1:])
    chain(first, rest).block_until_ready()
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        chain(first, rest).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return round(best / reps * 1e6, 2)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    from infinistore_tpu.ops import sparse_select as ss

    if jax.default_backend() != "tpu":
        sys.exit("time_admit_select: no TPU; a CPU time is not a device "
                 "time")
    todo = stages(ss)
    lines = []
    for s in ROWS:
        ops = operands(s)
        ops["scores"] = jax.jit(todo["index"][0])(
            ops["qi"], ops["w"], ops["keys"])
        ops["idx"] = jax.jit(todo["topk"][0])(ops["scores"], ops["pos"])
        for name, (fn, takes) in todo.items():
            row = {"stage": name, "rows": s, "call_us": time_chain(
                fn, [ops[k] for k in takes], args.reps, args.rounds),
                "device": jax.devices()[0].device_kind}
            lines.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)


if __name__ == "__main__":
    main()
