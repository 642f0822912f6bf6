#!/usr/bin/env python3
"""time_admit_select — ONE block of 64 queries of ONE layer of an
admission under a learned selection over K and V rows
(ops/sparse_select.py), alone on the chip at keye-vl2-30b-a3b's shapes:
16 index heads over keys of 128 lanes (64 of them zero), `topk` 2,048,
32 query heads over 4 kv heads of 128, S contiguous rows of prefix +
suffix. The two forms the attention under the selection can take
(PERF.md, PR 49), and the stages both share:

  index      the block's index scores [64, S]
  topk       the selection as it was until PR 50: `jax.lax.top_k` over
             them, a sort (composed here; not in the tree)
  threshold  `threshold`: the scores' keys and the kth largest of a row
             by bisection over the keys' bits, as the tree runs it on
             this backend (the chip: ONE Pallas call, a row's keys held
             in VMEM through 32 one-bit passes)
  kth_loop   the bisection alone over the keys as the XLA loop that
             runs off the chip (`_kth_key_loop`), `--bits` of the key a
             pass (a row for each: the table that chose the form)
  mask       `taken_from`: the mask of a threshold, ties by position
  compact    `positions_of`: a mask's positions in ascending order
             (what glm-5.2's admissions and an open tap add)
  masked     (ii) `taken_mask` of the scores and `attend_masked` over
             ALL S rows under it: no gather, 17 x the FLOPs at 35k
  flash      (ii) on the chip since PR 54: `block_attention`'s call a
             block over a GIVEN mask, ONE Pallas call
             (ops/pallas_masked_attention.py) over K and V laid by kv
             head (the relay, once a layer's 64 blocks, is not in the
             row), `--block-k` keys a tile (a row for each);
             `flash_half`: the block whose last query stands at S / 2,
             so half the key tiles are dead (`key_tiles`: run, of the
             rows')
  gathered   (i) the selected K and V rows gathered (2 x 64 x 2,048
             rows of 1 KB) and `attend_grouped` over them
  block_*    the whole block by each form (index + selection +
             attention): `block_flash` as `select_attend_seq` runs (ii)
             on the chip, `block_masked` as it runs it elsewhere and
             ran it until PR 54; `block_masked_sorted`: (ii) as PR 49
             ran it, the mask read from a sort's last score taken

A piece of 4,096 tokens is 64 such blocks in each of 5 layers. The form
NOT kept in the tree, (i), is composed here from the pieces the decode
step uses. Time as tools/time_select_decode.py takes it: R calls
chained inside one jitted loop on the first operand, wall time / R, the
best of a few repeats. One JSON line a case; `--out` also writes them
to a file (under chiprun_out/ on the chip).

  chiprun -- python3 tools/time_admit_select.py --out chiprun_out/admit_select.jsonl
"""

import argparse
import functools
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


def stages(ss, pm):
    import jax
    import jax.numpy as jnp

    scale = HEAD_DIM ** -0.5

    def flash(qa, taken, pos, kt, vt, block_k=pm.BLOCK_K):
        return pm.masked_flash_attention(qa, kt, vt, taken, jnp.max(pos) + 1,
                                         scale=scale, block_k=block_k)

    def block_flash(qi, w, pos, qa, keys, kt, vt):
        return flash(qa, ss.taken_mask(index(qi, w, keys), pos + 1, TOPK),
                     pos, kt, vt)

    def index(qi, w, keys):
        return ss._scores(qi, w, keys, "qhd,sd->qhs")

    def topk(scores, pos):
        live = jnp.arange(scores.shape[-1])[None] <= pos[:, None]
        return jax.lax.top_k(jnp.where(live, scores, -jnp.inf), TOPK)[1]

    def threshold(scores, pos):
        return ss.threshold(scores, pos + 1, TOPK)[1]

    def kth_loop(keys, pos):
        return ss._kth_key_loop(keys, jnp.minimum(pos + 1, TOPK))

    def mask(keys, edge, pos):
        return ss.taken_from(keys, edge, jnp.minimum(pos + 1, TOPK))

    def compact(taken):
        return ss.positions_of(taken, TOPK)[0]

    def masked(qa, scores, pos, k, v):
        return ss.attend_masked(qa, k, v,
                                ss.taken_mask(scores, pos + 1, TOPK), scale)

    def masked_sorted(qa, scores, pos, k, v):
        # the parent's `taken_mask`: above the last score a sort took,
        # and of its equals up to the highest position taken
        n_live = pos[:, None] + 1
        at = jnp.arange(scores.shape[-1])[None]
        top, idx = jax.lax.top_k(jnp.where(at < n_live, scores, -jnp.inf),
                                 TOPK)
        taken = jnp.arange(TOPK)[None] < jnp.minimum(n_live, TOPK)
        edge = jnp.min(jnp.where(taken, top, jnp.inf), axis=-1,
                       keepdims=True)
        last = jnp.max(jnp.where(taken & (top == edge), idx, -1), axis=-1,
                       keepdims=True)
        return ss.attend_masked(qa, k, v, (at < n_live) & (
            (scores > edge) | ((scores == edge) & (at <= last))), scale)

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

    def block_masked_sorted(qi, w, pos, qa, keys, k, v):
        return masked_sorted(qa, index(qi, w, keys), pos, k, v)

    def block_gathered(qi, w, pos, qa, keys, k, v):
        idx, taken = ss.select(index(qi, w, keys), pos + 1, TOPK)
        return ss.attend_grouped(qa, jnp.take(k, idx, axis=0),
                                 jnp.take(v, idx, axis=0), taken, scale)

    return {
        "index": (index, ("qi", "w", "keys")),
        "topk": (topk, ("scores", "pos")),
        "threshold": (threshold, ("scores", "pos")),
        "kth_loop": (kth_loop, ("skeys", "pos")),
        "mask": (mask, ("skeys", "edge", "pos")),
        "compact": (compact, ("taken",)),
        "attend_all_rows": (mask_only, ("qa", "scores", "pos", "k", "v")),
        "masked": (masked, ("qa", "scores", "pos", "k", "v")),
        "flash": (flash, ("qa", "taken", "pos", "kt", "vt")),
        "flash_half": (flash, ("qa", "taken_half", "pos_half", "kt", "vt")),
        "gathered": (gathered, ("qa", "idx", "k", "v")),
        "block_masked": (block_masked,
                         ("qi", "w", "pos", "qa", "keys", "k", "v")),
        "block_flash": (block_flash,
                        ("qi", "w", "pos", "qa", "keys", "kt", "vt")),
        "block_masked_sorted": (block_masked_sorted,
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
    ap.add_argument("--bits", default="",
                    help="widths of a bisection pass to time `kth_loop` "
                    "at, divisors of 32, e.g. 1,2,4 (default: the file's "
                    "_PASS_BITS)")
    ap.add_argument("--block-k", default="",
                    help="keys a tile to time `flash` at, e.g. "
                    "512,1024,2048 (default: the kernel's BLOCK_K)")
    ap.add_argument("--stages", default="",
                    help="the stages to time, e.g. masked,flash "
                    "(default: all)")
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    from infinistore_tpu.ops import pallas_masked_attention as pm
    from infinistore_tpu.ops import sparse_select as ss

    if jax.default_backend() != "tpu":
        sys.exit("time_admit_select: no TPU; a CPU time is not a device "
                 "time")
    todo = stages(ss, pm)
    wanted = [n for n in args.stages.split(",") if n] or list(todo)
    widths = [int(b) for b in args.bits.split(",") if b] or [ss._PASS_BITS]
    tiles = [int(b) for b in args.block_k.split(",") if b] or [pm.BLOCK_K]
    swept = {"kth_loop": ("pass_bits", widths), "flash": ("block_k", tiles),
             "flash_half": ("block_k", tiles)}
    lines = []
    for s in ROWS:
        ops = operands(s)
        ops["scores"] = jax.jit(todo["index"][0])(
            ops["qi"], ops["w"], ops["keys"])
        ops["skeys"], ops["edge"], _ = jax.jit(
            lambda s, p: ss.threshold(s, p + 1, TOPK))(
                ops["scores"], ops["pos"])
        ops["taken"] = jax.jit(todo["mask"][0])(ops["skeys"], ops["edge"],
                                                ops["pos"])
        ops["idx"] = jax.jit(todo["compact"][0])(ops["taken"])
        ops["kt"], ops["vt"] = pm.by_head(ops["k"]), pm.by_head(ops["v"])
        ops["pos_half"] = ops["pos"] - s // 2
        ops["taken_half"] = ss.taken_mask(ops["scores"],
                                          ops["pos_half"] + 1, TOPK)
        for name in wanted:
            fn, takes = todo[name]
            key, values = swept.get(name, (None, [None]))
            kept = ss._PASS_BITS
            for value in values:
                row = {"stage": name, "rows": s}
                if key == "pass_bits":
                    ss._PASS_BITS = value   # read while a stage is traced
                    row[key] = value
                elif key == "block_k":
                    fn = functools.partial(todo[name][0], block_k=value)
                    row.update(block_k=value, key_tiles=pm.tiles_run(
                        s, int(ops[takes[2]][-1]) + 1, value))
                row.update(call_us=time_chain(
                    fn, [ops[k] for k in takes], args.reps, args.rounds),
                    device=jax.devices()[0].device_kind)
                lines.append(row)
                print(json.dumps(row), flush=True)
            ss._PASS_BITS = kept
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)


if __name__ == "__main__":
    main()
