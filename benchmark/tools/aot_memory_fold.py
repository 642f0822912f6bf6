#!/usr/bin/env python3
"""Sandbox script (no chip): `aot_memory.py` for a configuration whose
finished windows fold (evabyte): its programs' shapes are of cache ROWS,
which `traffic.shapes` (positions) does not give. Compiles for a
DESCRIBED v5e at the real widths and prints each program's
memory_analysis(): the decode step over the table of rows, the cold
piece of a window, a piece of a window over the deepest table of
summary pages, the deepest tail, the largest hit (its suffix over the
restored summary and exact pages), and the fold.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_fold.py \
        --config evabyte --traffic docs24k-bytes

Nothing runs; a compile that passes is not a chip run.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools.aot_memory import (  # noqa: E402
    backend_answers_tpu, engine_pools)


def hit_shapes(spec, window, page):
    """(suffix positions, restored pages) of every hit's FIRST piece
    in the mix, and (positions, prefix pages) of every cold tail."""
    from benchmark.lib import traffic

    per_w, out = window // page, window // page // page
    hits, tails, deepest = set(), set(), 0
    for c in spec["classes"]:
        for t in traffic.turn_lengths(c, spec["turns"], page):
            w = t["prompt"] // window
            deepest = max(deepest, w)
            if not t["hit"]:
                tails.add((t["prompt"] - w * window, w * out))
                continue
            h = t["hit"] // page
            hw = h // per_w
            first = min(t["suffix"], (hw + 1) * window - t["hit"])
            hits.add((first, hw * out + h - hw * per_w))
    return sorted(hits), sorted(tails), deepest


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--programs",
                    default="decode,cold,piece,tail,hit,fold")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import serve, traffic
    from infinistore_tpu import serving

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    conf = serve.load_config(f"benchmark/configs/{args.config}.json")
    model, cfg = serve.model_config(conf)
    spec = traffic.load(f"benchmark/traffic/{args.traffic}.json")
    s = conf["serving"]
    window, page = cfg.fold_window, cfg.page_size
    hits, tails, deepest = hit_shapes(spec, window, page)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: model.init_params(k, cfg),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    weight_bytes = sum(int(x.size) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    held = engine_pools(model, cfg, serve.serving_config(conf, args.config))
    k_pool, v_pool = (sds(held[k].shape, held[k].dtype)
                      for k in ("k_pages", "v_pages"))
    print(json.dumps({
        "config": args.config, "weights_bytes": weight_bytes,
        "pool_bytes": sum(v.size * v.dtype.itemsize for v in held.values()),
        "hits": hits, "tails": tails, "deepest_window": deepest}),
        flush=True)
    i32 = jnp.int32
    slots = sds((s["max_slots"],), i32)
    rows = sds((s["max_slots"], s["max_pages_per_seq"]), i32)
    ids = sds((s["max_pages_per_seq"],), i32)

    def prefix(suffix, n):
        restored = sds((n * 2 * k_pool.shape[0], *k_pool.shape[2:]),
                       k_pool.dtype)
        return serving._admit_fused_px.lower(
            params, cfg, sds((1, suffix), i32), restored, k_pool, v_pool,
            sds((n,), i32), ids, sds((), i32), sds((), i32), model=model)

    out_pages = window // page // page
    hit = max(hits, key=lambda h: h[1])
    tail = max(tails, key=lambda t: t[0] * t[1])
    programs = {
        "decode": (s["max_slots"], lambda: serving._decode_fused.lower(
            params, cfg, slots, slots, k_pool, v_pool, rows, model=model)),
        "cold": (window, lambda: serving._admit_fused.lower(
            params, cfg, sds((1, window), i32), k_pool, v_pool, ids,
            sds((), i32), model=model)),
        "piece": ([window, deepest * out_pages],
                  lambda: prefix(window, deepest * out_pages)),
        "tail": (list(tail), lambda: prefix(*tail)),
        "hit": (list(hit), lambda: prefix(*hit)),
        "fold": (window // page, lambda: serving._fold_window.lower(
            params, cfg, k_pool, v_pool, sds((window // page,), i32),
            model=model)),
    }
    for name in args.programs.split(","):
        shape, lower = programs[name]
        t0 = time.perf_counter()
        try:
            with backend_answers_tpu():
                lowered = lower()
            ma = lowered.compile().memory_analysis()
            out = {"arguments": ma.argument_size_in_bytes,
                   "outputs": ma.output_size_in_bytes,
                   "aliased": ma.alias_size_in_bytes,
                   "temporaries": ma.temp_size_in_bytes}
            out["total_live"] = (out["arguments"] + out["outputs"]
                                 - out["aliased"] + out["temporaries"])
        except Exception as e:  # the compiler's refusal is the answer
            out = {"refused": f"{type(e).__name__}: {str(e)[:400]}"}
        out.update(program=name, shape=shape,
                   compile_s=round(time.perf_counter() - t0, 1))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
