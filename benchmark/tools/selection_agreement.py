#!/usr/bin/env python3
"""Chip tool, run once when a configuration with a learned selection is
brought up: how far the PROGRAM's selection and the float32
REFERENCE's agree at sampled decode positions. One prompt is admitted
through the engine as the cell admits it (in pieces, bf16 rows and
index keys in the pools), a few tokens are decoded, and at each decode
position the selection the decode program makes over the engine's own
pools (models/glm.py `decode_selections`) is compared with the
reference's own top-k at that position, per layer that owns an
indexer: rows in both, rows only the program took, and for each of
those where the reference ranked it and how far its float32 score lay
under the last score the reference took, as a share of that score's
distance from the median selected score. Expected: the sets differ
only in rows whose float32 scores lie within bf16's error of the
2,048th. Before the prompt is admitted its FIRST-TOKEN ROW is taken
through the cold program in pieces (`first_token_logits`) and held
to the reference's row at the prompt's last position: the served
program's side of `logit_tol`, a seed a line (`--seeds`: fresh weights
and prompt each, the compiled programs shared).

    python3 benchmark/tools/selection_agreement.py --config glm-5.2 \
        [--length 16496 --decode 4 --seeds N,N --rehearsal]
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def one_seed(seed, args, conf, model, cfg, ref, serve, jax, np):
    from infinistore_tpu.serving import Request, ServingEngine

    params = serve.init_weights(model, cfg, seed)
    prompt = [int(t) for t in np.random.default_rng(seed).integers(
        0, cfg.vocab_size, args.length)]
    eng = ServingEngine(params, cfg, serve.serving_config(conf, "agree"),
                        model=model)
    eng._proven = lambda active: False  # a step at a time
    row, hit = eng.first_token_logits(prompt)
    eng.submit(Request("a", prompt, max_new_tokens=args.decode + 1))
    tapped = jax.jit(model.decode_selections, static_argnums=1)
    ours = []  # per decode position: [(positions, taken) an owner]
    while len(ours) < args.decode:
        eng.step()
        slot = eng.slots[0]
        if slot is None or slot.todo or not slot.generated:
            continue
        token = np.zeros(eng.sc.max_slots, np.int32)
        lens = np.zeros(eng.sc.max_slots, np.int32)
        table = np.zeros_like(eng.page_table)
        token[0], lens[0] = slot.generated[-1], slot.seq_len
        table[0] = eng.page_table[0]
        taps = tapped(eng.params, cfg, token, lens, eng.k_pages,
                      eng.v_pages, table)
        ours.append((slot.seq_len,
                     [(np.asarray(i[0]), np.asarray(t[0])) for i, t in taps]))
    seq = prompt + list(slot.generated)
    del eng, slot, taps
    at = [pos for pos, _ in ours]
    rows, margins, theirs = ref.forward_with_selection(
        params, conf, np.asarray(seq[:at[-1] + 1], np.int32),
        [len(prompt) - 1] + at)
    theirs = {layer: tuple(part[1:] for part in parts[:4])
              + (tuple(part[1:] for part in parts[4]),)
              for layer, parts in theirs.items()}
    want_row = np.asarray(rows, np.float32)[0]
    print("selection_agreement: " + json.dumps({
        "seed": seed, "first_token_row_of": len(prompt),
        "hit_pages": int(hit),
        "program_against_reference_max_logit_diff": round(float(
            np.abs(np.asarray(row, np.float32) - want_row).max()), 4),
        "router_margin_least": round(float(
            np.asarray(margins)[0].min()), 5),
        "max_abs_logit": round(float(np.abs(want_row).max()), 3),
        "device": jax.devices()[0].device_kind}), flush=True)
    for layer_rank, layer in enumerate(sorted(theirs)):
        idx, taken, top, gap, (deep_idx, deep_top) = theirs[layer]
        for p, (pos, taps) in enumerate(ours):
            mine = set(taps[layer_rank][0][taps[layer_rank][1]].tolist())
            want = set(idx[p][taken[p]].tolist())
            rank = {int(r): j for j, r in enumerate(deep_idx[p])}
            edge, mid = float(top[p, -1]), float(np.median(top[p]))
            extra = sorted(mine - want)
            ranks = [rank.get(r, -1) for r in extra]
            under = [(edge - float(deep_top[p, j])) / (mid - edge)
                     for j in ranks if j >= 0]
            print("selection_agreement: " + json.dumps({
                "seed": seed, "layer": layer, "position": pos, "selected": len(want),
                "in_both": len(mine & want), "only_program": len(extra),
                "only_reference": len(want - mine),
                "agreement": round(len(mine & want) / len(want), 5),
                "program_rows_deepest_reference_rank": max(ranks,
                                                           default=None),
                "program_rows_beyond_twice_k": sum(j < 0 for j in ranks),
                "worst_score_under_edge_over_edge_to_median": round(
                    max(under, default=0.0), 5),
                "reference_gap_at_edge": float(gap[p]),
                "edge_score": edge, "median_selected_score": mid,
                "device": jax.devices()[0].device_kind}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="2147484101")
    ap.add_argument("--length", type=int, default=16496)
    ap.add_argument("--decode", type=int, default=4)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmark.lib import serve
    conf = serve.load_config(f"benchmark/configs/{args.config}.json",
                             args.rehearsal)
    model, cfg = serve.model_config(conf)
    ref = serve.reference_module(conf)
    for seed in (int(x) for x in args.seeds.split(",")):
        one_seed(seed, args, conf, model, cfg, ref, serve, jax, np)
        gc.collect()  # the next seed needs this one's HBM back
    return 0


if __name__ == "__main__":
    sys.exit(main())
