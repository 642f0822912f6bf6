#!/usr/bin/env python3
"""Sandbox script (no chip): benchmark/tools/aot_memory_index.py's
question for a configuration whose K and V cache has a THIRD kind of
page (index keys on every layer: keye-vl2-30b-a3b; the engine holds a
K, a V and an index pool under one page id, the last two as the pair's
second place) and whose attention reads a learned selection. Compiles
for a DESCRIBED v5e at the real widths the decode program, the cold
program of one piece, the prefix program of a piece over the longest
prefix a cold prompt reaches, the largest hit of the traffic, and the
gathers that read a slot's pages back out of a K or V pool and out of
the index pool for a piece, and prints each one's memory_analysis();
the pools are the engine's own (its constructor's shapes, nothing
allocated). Nothing runs; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_kvi.py \
        --config keye-vl2-30b-a3b --traffic docs32k-answers-kvi
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools.aot_memory import backend_answers_tpu  # noqa: E402


def engine_pools(model, cfg, sconfig):
    """aot_memory.engine_pools for an engine whose second pool is a
    pair: the shapes of `k_pages` and `v_pages` from the engine's own
    constructor, nothing allocated."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.serving import ServingEngine

    weights = {"placeholder": jnp.zeros(())}

    def build():
        eng = ServingEngine(weights, cfg, sconfig, model=model)
        return {"k_pages": eng.k_pages, "v_pages": eng.v_pages}

    return jax.eval_shape(build)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--piece", type=int, default=0,
                    help="admit_piece (default: the configuration's)")
    ap.add_argument("--programs",
                    default="decode,cold,piece,hit,gather,gather_index")
    ap.add_argument("--hlo", default="",
                    help="directory to write each program's optimized "
                         "HLO text to")
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
    shapes = traffic.shapes(spec, cfg.page_size)
    s = conf["serving"]
    page = cfg.page_size
    piece = args.piece or s["admit_piece"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: model.init_params(k, cfg),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    weight_bytes = sum(int(x.size) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    held = engine_pools(model, cfg, serve.serving_config(conf, args.config))
    by_kind = dict(zip(cfg.page_kinds, serving._kind_pools(
        held["k_pages"], held["v_pages"])))
    pool, vpool, ipool = (sds(p.shape, p.dtype) for p in by_kind.values())
    second = (vpool, ipool)
    print(json.dumps({
        "config": args.config, "weights_bytes": weight_bytes,
        "engine_holds": {k: [list(v.shape), str(v.dtype)]
                         for k, v in by_kind.items()},
        "pool_bytes": sum(v.size * v.dtype.itemsize
                          for v in by_kind.values()),
        "admit_piece": piece}), flush=True)
    i32 = jnp.int32
    cold = max(shapes["cold"])
    before = (cold - 1) // piece * piece - piece
    sfx, pfx = max(shapes["prefix"], key=lambda p: p[0] * (p[0] + p[1]))
    slots = sds((s["max_slots"],), i32)
    rows = sds((s["max_slots"], s["max_pages_per_seq"]), i32)
    ids = sds((s["max_pages_per_seq"],), i32)

    def restored(pages):
        return tuple(sds((pages * p.shape[0], *p.shape[2:]), p.dtype)
                     for p in (pool, vpool, ipool))

    def prefix(tokens, pages):
        return serving._admit_fused_px.lower(
            params, cfg, sds((1, tokens), i32), restored(pages), pool,
            second, sds((pages,), i32), ids, sds((), i32), sds((), i32),
            model=model)

    programs = {
        "decode": lambda: serving._decode_fused.lower(
            params, cfg, slots, slots, pool, second, rows, model=model,
            fetched=True),
        "cold": lambda: serving._admit_fused.lower(
            params, cfg, sds((1, piece), i32), pool, second, ids,
            sds((), i32), model=model),
        "piece": lambda: prefix(piece, before // page),
        "hit": lambda: prefix(sfx, pfx // page),
        "gather": lambda: serving._gather_pages.lower(
            pool, None, sds((before // page,), i32)),
        "gather_index": lambda: serving._gather_pages.lower(
            ipool, None, sds((before // page,), i32)),
    }
    for name in args.programs.split(","):
        t0 = time.perf_counter()
        try:
            with backend_answers_tpu():
                lowered = programs[name]()
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            out = {"arguments": ma.argument_size_in_bytes,
                   "outputs": ma.output_size_in_bytes,
                   "aliased": ma.alias_size_in_bytes,
                   "temporaries": ma.temp_size_in_bytes}
            out["total_live"] = (out["arguments"] + out["outputs"]
                                 - out["aliased"] + out["temporaries"])
            if args.hlo:
                os.makedirs(args.hlo, exist_ok=True)
                with open(os.path.join(args.hlo, name + ".hlo"), "w") as f:
                    f.write(compiled.as_text())
        except Exception as e:  # the compiler's refusal is the answer
            out = {"refused": f"{type(e).__name__}: {str(e)[:600]}"}
        out.update(program=name,
                   shape={"decode": s["max_slots"], "cold": piece,
                          "piece": [piece, before], "hit": [sfx, pfx],
                          "gather": before // page,
                          "gather_index": before // page}[name],
                   compile_s=round(time.perf_counter() - t0, 1))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
