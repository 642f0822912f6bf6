#!/usr/bin/env python3
"""Chip tool, run once when phi4-mini-flash's tolerance is set: the
readings a limit is set between, as first-token logit rows of one
prompt against the float32 reference.

First reading, the program as it is: the cold admission (one row above
layer 17) and a hit (the last `--suffix` tokens over the rest as a
prefix: the full layer's rows, the banded layers' last band, the state
at the edge).

Second readings, each of which has to come out as NOT correct:
  bf16_state        the hit continues from a state rounded to bfloat16
                    where float32 is stated
  band_minus_a_page the banded layers' band a page short (496 of 512)
  no_lambda         the second softmax map left out (lam = 0)
  memory_after_gate the Gated Memory Units read layer 16's output AFTER
                    its gate
  one_cross_blind   the last of the 7 cross layers does not read layer
                    17's cache: its attention gives zeros
  all_cross_blind   none of the 7 does
  fp8_reference     the reference itself on matrices rounded to
                    float8_e4m3fn, the nearest precision below the
                    configuration's bfloat16

The DECODE step (`--decode N`: N steps of `--slots` sequences at
once, each prefilled to another depth, their pages in the three kinds
of pool as the engine lays them: the full layer's under a page table,
the banded layers' last band under a short table, the state rows),
every step's logits rows against the reference and, as `correct`
takes an answered token, the reference's maximum less its logit of the
token the row would pick. The program, then with a fault planted in
the seven calls that read the full layer's pages a second time:
  one_borrower_blind    the last gives zeros
  all_borrowers_blind   all seven do
  borrowers_next_table  they read through the NEXT slot's page table
`--harness FAULT -- <arguments of benchmark/run.py>` runs the cell
itself with a decode fault planted, so that `correct` decides on it.
`--rehearsal` runs the tool's own paths at the configuration's tiny
widths on the CPU (its numbers say nothing of the chip's).

    chiprun -- python3 benchmark/tools/precision_reading_three.py \
        --config phi4-mini-flash --out chiprun_out/precision_phi.jsonl
"""

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAULTS = ("one_cross_blind", "all_cross_blind", "memory_after_gate",
          "band_minus_a_page", "no_lambda")
DECODE_FAULTS = ("one_borrower_blind", "all_borrowers_blind",
                 "borrowers_next_table")


def planted(name, decoder, cfg):
    """(cfg, undo) with the fault `name` planted in models/decoder.py
    for the programs traced until `undo()`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    held = {}

    def patch(attr, value):
        held[attr] = getattr(decoder, attr)
        setattr(decoder, attr, value)

    if name == "band_minus_a_page":
        cfg = dataclasses.replace(cfg, layer_bands=tuple(
            w - cfg.page_size if w else 0 for w in cfg.layer_bands))
    elif name == "no_lambda":
        patch("diff_lambda", lambda layer, depth: (
            jnp.float32(0.0), 0.8 - 0.6 * float(np.exp(-0.3 * depth))))
    elif name == "memory_after_gate":
        real = decoder._mamba1_out

        def gated(layer, y, xs, z):
            out, mem = real(layer, y, xs, z)
            return out, (mem.astype(jnp.float32) * jax.nn.silu(
                z.astype(jnp.float32))).astype(mem.dtype)

        patch("_mamba1_out", gated)
    elif name in ("one_cross_blind", "all_cross_blind"):
        real_row, calls = decoder._attend_row, []
        n_cross = sum(kind == "cross" for kind in cfg.layer_kinds)

        def blind(q, k, v, last):
            calls.append(1)
            out = real_row(q, k, v, last)
            # the cut layer's own call, then one a cross layer: the
            # last one's attention (or every one's) gives nothing
            nth = (len(calls) - 1) % (1 + n_cross)
            if nth == n_cross or (nth and name == "all_cross_blind"):
                out = jnp.zeros_like(out)
            return out

        patch("_attend_row", blind)
    elif name in DECODE_FAULTS:
        real_call, calls = decoder.paged_decode_attention, []
        kinds = cfg.layer_kinds
        n_cross = sum(kind == "cross" for kind in kinds)
        n_own = sum(kind == "attention" for kind in kinds)

        def faulty(q, kp, vp, table, lens, **kw):
            # a step's calls in the stack's order: the layers that own
            # their pages, then the borrowers
            calls.append(1)
            nth = (len(calls) - 1) % (n_own + n_cross) - n_own
            if nth >= 0 and name == "borrowers_next_table":
                table = jnp.roll(table, 1, axis=0)
            out = real_call(q, kp, vp, table, lens, **kw)
            if name == "all_borrowers_blind" and nth >= 0 \
                    or name == "one_borrower_blind" and nth == n_cross - 1:
                out = jnp.zeros_like(out)
            return out

        patch("paged_decode_attention", faulty)

    def undo():
        for attr, value in held.items():
            setattr(decoder, attr, value)

    return cfg, undo


def decode_rows(args, model, cfg, ref, conf, decoder, params, pre, seed,
                toks, pad_to):
    """{reading: {"logit": worst |logit difference| of a decode step's
    row, "deficit": worst reference maximum less the reference's logit
    of the token the row picks}} over `args.decode` steps of
    `args.slots` sequences at once, the program and each decode
    fault."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    page, steps, slots = cfg.page_size, args.decode, args.slots
    band = max(cfg.layer_bands)
    rng = np.random.default_rng(seed + 1)
    depths = [args.length, args.length - args.length // 8 - 7,
              args.length // 2 + 3, args.length // 4 + 9][:slots]
    seqs = [np.concatenate([toks[:depths[0]] if i == 0 else rng.integers(
        0, cfg.vocab_size, n), rng.integers(0, cfg.vocab_size, steps)]
        ).astype(np.int32) for i, n in enumerate(depths)]
    # pages a slot at most: of the full layer, and of a banded one
    # (its band, the steps, the page the band's floor lies in); the
    # page table as wide as the configuration's, page 0 the scratch
    per = -(-(depths[0] + steps) // page) + 1
    wper = -(-(band + steps) // page) + 2
    wide = max(per, conf["serving"]["max_pages_per_seq"])
    shape = cfg.kv_page_shape()
    n_win = sum(1 for b, *_ in decoder.attn_layers(cfg) if b)
    kp = jnp.zeros((1, 1 + slots * per, *shape), cfg.jdtype)
    wk = jnp.zeros((n_win, 1 + slots * wper, *shape), cfg.jdtype)
    vp, wv = kp, wk
    state = model.state_pools(cfg, slots)
    table = np.zeros((slots, wide), np.int32)
    wtable = np.zeros((slots, wper), np.int32)
    wbase = np.zeros(slots, np.int32)
    want = []
    for i, (n, seq) in enumerate(zip(depths, seqs)):
        padded = np.zeros(pad_to, np.int32)
        padded[:len(seq)] = seq
        want.append(np.asarray(ref.forward(
            params, conf, padded, list(range(n, n + steps)))[0]))
        first = np.zeros(args.length, np.int32)
        first[:n] = seq[:n]
        kvs, states = pre(params, jnp.asarray(first[None]), jnp.int32(n))
        ids = 1 + i * per + np.arange(per)
        table[i, :per] = ids
        n_pages = -(-n // page)
        # the banded layers keep what their band can reach, from the
        # page its floor lies in, under the short table
        floor = max(0, (n - band + 1) // page)
        wbase[i] = floor * page
        wids = 1 + i * wper + np.arange(wper)
        wtable[i] = wids

        def paged(a):
            a = jnp.pad(a[0, :n], ((0, n_pages * page - n), (0, 0), (0, 0)))
            return a.reshape(n_pages, *shape)

        for (b, _, pool, li), (k, v) in zip(decoder.attn_layers(cfg), kvs):
            if pool == "full":
                kp = kp.at[li, ids[:n_pages]].set(paged(k))
                vp = vp.at[li, ids[:n_pages]].set(paged(v))
            else:
                at = wids[:n_pages - floor]
                wk = wk.at[li, at].set(paged(k)[floor:])
                wv = wv.at[li, at].set(paged(v)[floor:])
        for j, st in enumerate(states):
            state["h"][j] = state["h"][j].at[i].set(st["h"][0])
            state["conv"][j] = state["conv"][j].at[i].set(st["conv"][0])
        del kvs, states
    held = (kp, vp, state, wk, wv)
    table, wtable, wbase = (jnp.asarray(a) for a in (table, wtable, wbase))
    out = {}
    for name in ("program",) + DECODE_FAULTS:
        bad, undo = (cfg, lambda: None) if name == "program" \
            else planted(name, decoder, cfg)
        # models' decode_step is jitted on a static cfg: what it traced
        # for the reading before must not answer for this one
        jax.clear_caches()
        step = jax.jit(lambda p, tok, lens, kp, vp, st, wk, wv: (
            model.decode_step(p, bad, tok, lens, kp, vp, table, st,
                              win=(wk, wv, wtable, wbase))))
        kp, vp, state, wk, wv = held    # every reading from the same pools
        logit = deficit = 0.0
        try:
            for j in range(steps):
                tok = jnp.asarray([seq[n + j] for n, seq in
                                   zip(depths, seqs)], jnp.int32)
                lens = jnp.asarray([n + j for n in depths], jnp.int32)
                rows, kp, vp, state, wk, wv = step(params, tok, lens, kp,
                                                   vp, state, wk, wv)
                rows = np.asarray(rows, np.float32)
                for i in range(slots):
                    logit = max(logit, float(np.abs(
                        rows[i] - want[i][j]).max()))
                    deficit = max(deficit, float(
                        want[i][j].max() - want[i][j][rows[i].argmax()]))
        finally:
            undo()
        out[name] = {"logit": logit, "deficit": deficit}
        print(f"reading: {seed} decode {name} {out[name]}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="2147484101,2147494111")
    ap.add_argument("--length", type=int, default=8304)
    ap.add_argument("--suffix", type=int, default=112)
    ap.add_argument("--readings", default="all")
    ap.add_argument("--decode", type=int, default=0,
                    help="decode steps read against the reference")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--harness", default="", choices=("",) + DECODE_FAULTS,
                    help="run benchmark/run.py (its arguments behind --) "
                         "with this decode fault planted")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("run", nargs="*")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import serve
    from infinistore_tpu.models import decoder

    conf = serve.load_config(f"benchmark/configs/{args.config}.json",
                             args.rehearsal)
    model, cfg = serve.model_config(conf)
    if args.harness:
        from benchmark import run

        planted(args.harness, decoder, cfg)
        print(f"planted: {args.harness}", flush=True)
        return run.main(args.run)
    if not args.rehearsal:
        serve.enable_compile_cache()
    ref = serve.reference_module(conf)
    page = cfg.page_size
    n, last = args.length - args.suffix, args.length - 1
    assert n % page == 0 and args.length % page == 0
    wanted = FAULTS + ("bf16_state", "fp8_reference") \
        if args.readings == "all" else tuple(args.readings.split(","))
    # one length for every pass of the reference: one set of programs
    pad_to = -(-(args.length + args.decode) // 128) * 128

    def reference(params, toks, positions):
        padded = np.zeros(pad_to, np.int32)
        padded[:len(toks)] = toks
        return np.asarray(ref.forward(params, conf, padded, positions)[0])

    def cold_row(cfg):
        return jax.jit(lambda p, t: model.prefill(
            p, cfg, t, s_real=jnp.int32(args.length),
            last_only=True)[0][0, 0])

    def prefix_of(kvs):
        """Each layer's rows of the prefix it may attend: all of a full
        layer's, the last band of a banded one's."""
        return [(k[:, n - band:n] if band else k[:, :n],
                 v[:, n - band:n] if band else v[:, :n])
                for (band, *_), (k, v) in zip(decoder.attn_layers(cfg), kvs)]

    # the first `real` tokens of a row of `--length`: ONE program for
    # the hit's prefix and every depth a decode slot is prefilled to
    pre = jax.jit(lambda p, t, real: model.prefill(p, cfg, t,
                                                   s_real=real)[1:])
    hit = jax.jit(lambda p, t, kvs, st: model.prefill_with_prefix(
        p, cfg, t, kvs, state=st, s_real=jnp.int32(args.suffix),
        last_only=True)[0][0, 0])
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        params = serve.init_weights(model, cfg, seed)
        toks = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, args.length).astype(np.int32)
        want = reference(params, toks, [last])[0]
        row = {"seed": seed, "length": args.length,
               "cross_out_gain": getattr(cfg, "cross_out_gain", 1.0),
               "max_abs_logit": float(np.abs(want).max())}
        top = np.sort(want)[-2:]
        row["top1_minus_top2"] = float(top[1] - top[0])
        if not args.rehearsal:
            row["peak_gb_after_reference"] = (jax.devices()[
                0].memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9

        def diff(got):
            return float(np.abs(np.asarray(got, np.float32) - want).max())

        row["program_cold"] = diff(cold_row(cfg)(params,
                                                 jnp.asarray(toks[None])))
        print(f"reading: {seed} program_cold {row['program_cold']}",
              flush=True)
        for name in FAULTS:
            if name not in wanted:
                continue
            bad, undo = planted(name, decoder, cfg)
            try:
                row[name] = diff(cold_row(bad)(params,
                                               jnp.asarray(toks[None])))
            finally:
                undo()
            print(f"reading: {seed} {name} {row[name]}", flush=True)
        if "hit" in wanted or "bf16_state" in wanted:
            kvs, states = pre(params, jnp.asarray(toks[None]), jnp.int32(n))
            kvs = prefix_of(kvs)
            sfx = jnp.asarray(toks[None, n:])
            exact = [(s["h"], s["conv"]) for s in states]
            row["program_hit"] = diff(hit(params, sfx, kvs, exact))
            if "bf16_state" in wanted:
                low = [tuple(a.astype(jnp.bfloat16).astype(a.dtype)
                             for a in st) for st in exact]
                row["bf16_state"] = diff(hit(params, sfx, kvs, low))
            del kvs, states, exact
            print(f"reading: {seed} hit {row['program_hit']} bf16_state "
                  f"{row.get('bf16_state')}", flush=True)
        if args.decode:
            for name, got in decode_rows(args, model, cfg, ref, conf,
                                         decoder, params, pre, seed, toks,
                                         pad_to).items():
                row[f"decode_{name}"] = got
        if "fp8_reference" in wanted:
            # last, and in place of the weights, a leaf at a time: two
            # copies of 7.7 GB do not fit the chip. EAGERLY, two
            # converts a leaf: inside one jitted program the compiler
            # drops the round trip as excess precision it may keep
            # (the first form here read 0.0)
            leaves, tree = jax.tree_util.tree_flatten(params)
            del params
            for i, x in enumerate(leaves):
                if x.ndim >= 2:
                    leaves[i] = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
            params = jax.tree_util.tree_unflatten(tree, leaves)
            del leaves, x
            row["fp8_reference"] = diff(reference(params, toks, [last])[0])
        line = json.dumps(row)
        print("precision_reading: " + line, flush=True)
        lines.append(line)
        del params
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.writelines(line + "\n" for line in lines)


if __name__ == "__main__":
    sys.exit(main())
