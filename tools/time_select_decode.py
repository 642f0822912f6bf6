#!/usr/bin/env python3
"""time_select_decode — the stages of a decode step's learned selection
(ops/sparse_select.py) alone on the chip, at glm-5.2's shapes: 8 slots,
a page table of 2,192 pages of 16 (35,072 keys), 32 index heads of 128,
`index_topk` 2,048, latent rows of 640 bf16 lanes under 64 heads.

  index      `select_paged`'s first half: a slot's index keys through
             its page table and their scores
  topk       the selection as it was until PR 50: `jax.lax.top_k` over
             those scores, a sort (composed here; not in the tree)
  threshold  `threshold`: the scores' keys and the kth largest of a row
             by bisection over the keys' bits, as the tree runs it on
             this backend (the chip: ONE Pallas call, a row's keys held
             in VMEM through 32 one-bit passes)
  kth_loop   the bisection alone over the keys as the XLA loop that
             runs off the chip (`_kth_key_loop`), `--bits` of the key a
             pass (a row for each: the table that chose the form)
  mask       `taken_from`: the mask of a threshold, ties by position
  compact    `positions_of`: a mask's positions in ascending order
  exact      `select` over the scores: threshold, mask and compact
  select     `select_paged`: index and exact
  attend     `gather_paged` of the selected rows and `attend` over them

each over the first n = 1, 2, 4, 8 slots (the rungs of
`sparse_select.ladder(8)`: what a branch of `over_active` runs; a loop
of one sequence a turn would pay the n = 1 time a sequence), and
`select` and `attend` through `over_active` with 1, 2, 3, 5, 8 of the 8
slots valid (the conditional, the reorder and the branch together).

Time as tools/time_paged_decode.py takes it: R calls chained inside one
jitted loop, wall time / R, the best of a few repeats. Every operand of
a call hangs on the call before (the first by 1e-30 of its result, the
integer ones by a zero made of it), so that no gather is lifted out of
the loop. One JSON line a case; `--out` also writes them to a file
(under chiprun_out/ on the chip).

  chiprun -- python3 tools/time_select_decode.py --out chiprun_out/select.jsonl
"""

import argparse
import json
import os
import sys
import time

SLOTS, TABLE, PAGE, TOPK = 8, 2192, 16, 2048
INDEX_HEADS, INDEX_DIM, HEADS, WIDTH, RANK = 32, 128, 64, 640, 512
VALID = (1, 2, 3, 5, 8)


def operands():
    """Pools of one layer's worth of distinct pages a slot (and the
    scratch page 0), their table, lengths of 17-35k tokens, and a
    step's queries."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    pages = SLOTS * TABLE + 1
    table = 1 + np.random.default_rng(0).permutation(SLOTS * TABLE)
    return {
        "ipool": jax.random.normal(ks[0], (2, pages, PAGE, INDEX_DIM),
                                   jnp.bfloat16),
        "pool": jax.random.normal(ks[1], (2, pages, PAGE, WIDTH),
                                  jnp.bfloat16),
        "table": jnp.asarray(table.reshape(SLOTS, TABLE), jnp.int32),
        "n_live": jnp.asarray(np.linspace(17000, 35000, SLOTS), jnp.int32),
        "qi": jax.random.normal(ks[2], (SLOTS, INDEX_HEADS, INDEX_DIM),
                                jnp.bfloat16),
        "w": jax.random.uniform(ks[3], (SLOTS, INDEX_HEADS), jnp.float32),
        "q": jax.random.normal(ks[4], (SLOTS, HEADS, WIDTH), jnp.bfloat16),
    }


def stages(ss):
    """name: (fn over arrays with a leading slot axis -> arrays with a
    leading slot axis, the operands it takes, the pool it reads)."""
    import jax
    import jax.numpy as jnp

    def index(qi, w, table, ipool):
        keys = ipool.at[(1, table)].get(mode="clip")
        b, n, page, di = keys.shape
        return ss._scores(qi, w, keys.reshape(b, n * page, di),
                          "bhd,bsd->bhs")

    def topk(scores, n_live):
        live = jnp.arange(scores.shape[-1])[None] < n_live[:, None]
        return jax.lax.top_k(jnp.where(live, scores, -jnp.inf), TOPK)[1]

    def threshold(scores, n_live):
        return ss.threshold(scores, n_live, TOPK)[1]

    def kth_loop(keys, n_live):
        return ss._kth_key_loop(keys, jnp.minimum(n_live, TOPK))

    def mask(keys, edge, n_live):
        return ss.taken_from(keys, edge, jnp.minimum(n_live, TOPK))

    def compact(taken):
        return ss.positions_of(taken, TOPK)[0]

    def exact(scores, n_live):
        return ss.select(scores, n_live, TOPK)[0]

    def select(qi, w, table, n_live, ipool):
        return ss.select_paged(qi, w, table, n_live, ipool, 1, TOPK)[0]

    def attend(q, table, idx, pool):
        taken = jnp.ones(idx.shape, bool)
        return ss.attend(q, ss.gather_paged(pool, 1, table, idx), taken,
                         RANK)

    return {"index": (index, ("qi", "w", "table"), "ipool"),
            "topk": (topk, ("scores", "n_live"), None),
            "threshold": (threshold, ("scores", "n_live"), None),
            "kth_loop": (kth_loop, ("keys", "n_live"), None),
            "mask": (mask, ("keys", "edge", "n_live"), None),
            "compact": (compact, ("taken",), None),
            "exact": (exact, ("scores", "n_live"), None),
            "select": (select, ("qi", "w", "table", "n_live"), "ipool"),
            "attend": (attend, ("q", "table", "idx"), "pool")}


def time_chain(fn, arrays, pool, reps, rounds):
    """us a call of `fn(*arrays[, pool])`, `reps` calls chained on the
    first array."""
    import jax
    import jax.numpy as jnp

    more = () if pool is None else (pool,)

    @jax.jit
    def chain(first, rest, more):
        def one(_, carry):
            x, zero = carry
            out = fn(x, *(a + zero if a.dtype == jnp.int32 else a
                          for a in rest), *more)
            s = jnp.sum(out.astype(jnp.float32))
            return (x + (s * 1e-30).astype(x.dtype),
                    (s > 3e38).astype(jnp.int32))
        return jax.lax.fori_loop(0, reps, one, (first, jnp.int32(0)))[0]

    first, rest = arrays[0], tuple(arrays[1:])
    chain(first, rest, more).block_until_ready()
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        chain(first, rest, more).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return round(best / reps * 1e6, 2)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--bits", default="",
                    help="widths of a bisection pass to time `kth_loop` "
                    "at, divisors of 32, e.g. 1,2,4 (default: the file's "
                    "_PASS_BITS)")
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.ops import sparse_select as ss

    if jax.default_backend() != "tpu":
        sys.exit("time_select_decode: no TPU; a CPU time is not a device "
                 "time")
    ops = operands()
    todo = stages(ss)
    ops["scores"] = jax.jit(todo["index"][0])(
        ops["qi"], ops["w"], ops["table"], ops["ipool"])
    ops["keys"], ops["edge"], _ = jax.jit(
        lambda s, n: ss.threshold(s, n, TOPK))(ops["scores"], ops["n_live"])
    ops["taken"] = jax.jit(todo["mask"][0])(ops["keys"], ops["edge"],
                                            ops["n_live"])
    ops["idx"] = jax.jit(todo["compact"][0])(ops["taken"])
    lines = []

    def say(**row):
        row["device"] = jax.devices()[0].device_kind
        lines.append(row)
        print(json.dumps(row), flush=True)

    widths = [int(b) for b in args.bits.split(",") if b] or [ss._PASS_BITS]
    for name, (fn, takes, pool) in todo.items():
        pool = None if pool is None else ops[pool]
        kept = ss._PASS_BITS
        for bits in widths if name == "kth_loop" else [kept]:
            ss._PASS_BITS = bits        # read while a stage is traced
            for n in ss.ladder(SLOTS):
                row = {"pass_bits": bits} if name == "kth_loop" else {}
                say(stage=name, form="alone", slots_run=n, **row,
                    call_us=time_chain(fn, [ops[k][:n] for k in takes],
                                       pool, args.reps, args.rounds))
        ss._PASS_BITS = kept
    for name in ("select", "attend"):
        fn, takes, pool = todo[name]
        pool = ops[pool]

        def laddered(*xs, fn=fn):
            *arrays, order, rung, pool = xs
            return ss.over_active(lambda *a: fn(*a, pool), (order, rung),
                                  *arrays)

        for valid in VALID:
            # scattered: the valid slots from the top down
            active = ss.active_first(jnp.arange(SLOTS) >= SLOTS - valid)
            say(stage=name, form="over_active", slots_valid=valid,
                call_us=time_chain(
                    laddered, [ops[k] for k in takes] + list(active), pool,
                    args.reps, args.rounds))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)


if __name__ == "__main__":
    main()
