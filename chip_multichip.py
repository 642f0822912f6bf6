#!/usr/bin/env python3
"""Four-chip bring-up checks, run by the builder (the driver's smoke is
one chip): `chiprun --chips 4 -- python3 chip_multichip.py`.

One process drives all four chips, with the store as a JAX-free child:

  (a) the smoke's model and request script with the weights sharded
      tp=4 (parallel.mesh.shard_params); prints where weights and pool
      actually live and each chip's bytes_in_use;
  (b) two one-chip engines, chips 0 and 1, one store: turn 1 on chip 0,
      turn 2 on chip 1 must hit and restore onto chip 1;
  (c) decode_attention_tp and IciKVPool.fetch_from_store + handoff over
      the four chips at the model's head geometry, against references.

`--cpu-rehearsal` runs the same at a toy width on virtual CPU devices.
Sizes, the store child and the request script come from chip_smoke.py.
"""

import argparse
import json
import os
import sys
import tempfile
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
N_CHIPS = 4


def chip_bytes(jax):
    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()[:N_CHIPS]]


def tp_engine(size, cfg, params, conn, smoke):
    """(a) Weights sharded tp=4. On the CPU backend the engine serves
    over them under GSPMD; on TPU it refuses at construction, because
    its step programs call Pallas kernels GSPMD cannot partition — the
    lowering error is printed as the compiler words it."""
    import jax

    from infinistore_tpu import serving
    from infinistore_tpu.parallel import mesh as pmesh
    from infinistore_tpu.serving_http import ServingHTTPServer
    from infinistore_tpu.tpu import TpuKVStore

    mesh = pmesh.make_mesh(pmesh.MeshConfig(dp=1, tp=N_CHIPS),
                           jax.devices()[:N_CHIPS])
    sharded = pmesh.shard_params(mesh, params)
    weights = [0] * N_CHIPS
    for leaf in jax.tree_util.tree_leaves(sharded):
        for shard in leaf.addressable_shards:
            weights[shard.device.id] += shard.data.nbytes
    layer = sharded["layers"][0]
    print("  placement: " + json.dumps({
        "weights_bytes_per_chip": weights,
        "wq": str(layer["wq"].sharding.spec),
        "w_down": str(layer["w_down"].sharding.spec),
        "embed": str(sharded["embed"].sharding.spec),
        # chip 0 also holds the caller's unsharded copy of the weights
        "bytes_in_use_per_chip": chip_bytes(jax),
    }), flush=True)
    sconfig = serving.ServingConfig(model_id="tp4", **size["serving"])
    if jax.default_backend() == "tpu":
        try:
            serving.ServingEngine(sharded, cfg, sconfig)
            refused = None
        except NotImplementedError as e:
            refused = str(e)
        smoke.check(refused is not None,
                    f"engine refuses mesh-sharded weights on TPU: {refused}")
        try:
            smoke.lower_decode(sharded, cfg, sconfig)
            said = None
        except Exception as e:
            said = f"{type(e).__name__}: {e}"
        smoke.check(said is not None,
                    f"lowering the decode step over them fails with: {said}")
        return
    eng = serving.ServingEngine(sharded, cfg, sconfig,
                                store=TpuKVStore(conn))
    web = ServingHTTPServer(eng)
    try:
        d1, d2 = smoke.two_turns(
            f"http://127.0.0.1:{web.start()}", size, cfg.vocab_size, seed=1
        )
    finally:
        web.shutdown()
    smoke.check(d1["offloaded_pages"] > 0 and d2["prefix_hit_pages"] > 0
                and d2["prefill_tokens"] < d1["prefill_tokens"],
                f"tp=4 engine: offloaded {d1['offloaded_pages']} pages, "
                f"turn 2 hit {d2['prefix_hit_pages']}; pool placed by "
                f"GSPMD as {eng.k_pages.sharding.spec}")


def two_replicas(size, cfg, params, service_port, smoke):
    """(b) Prefix reuse across chips through the store."""
    import jax

    from infinistore_tpu import ClientConfig, InfinityConnection
    from infinistore_tpu.serving import ServingConfig, ServingEngine
    from infinistore_tpu.serving_http import ServingHTTPServer
    from infinistore_tpu.tpu import TpuKVStore

    class Recording(TpuKVStore):
        """Notes where every restore landed."""

        def get_kv_pages(self, *a, **kw):
            out = super().get_kv_pages(*a, **kw)
            self.restored_on = set(out.devices())
            return out

    conns, webs, engines = [], [], []
    try:
        for dev in jax.devices()[:2]:
            conn = InfinityConnection(ClientConfig(
                host_addr="127.0.0.1", service_port=service_port
            ))
            conn.connect()
            conns.append(conn)
            eng = ServingEngine(
                jax.device_put(params, dev), cfg,
                ServingConfig(model_id="replicas", **size["serving"]),
                store=Recording(conn),
            )
            engines.append(eng)
            webs.append(ServingHTTPServer(eng))
        bases = [f"http://127.0.0.1:{w.start()}" for w in webs]
        prompts, extras = smoke.conversations(size, cfg.vocab_size, seed=5)
        out1 = smoke.run_turn(bases[0], prompts, size["new"])
        before = chip_bytes(jax)
        smoke.run_turn(
            bases[1], [p + o + x for p, o, x in zip(prompts, out1, extras)],
            size["new"],
        )
        s0, s1 = (smoke.get_json(f"{b}/stats")["engine"] for b in bases)
        chip1 = jax.devices()[1]
        smoke.check(
            s0["offloaded_pages"] > 0 and s1["prefix_hit_pages"] > 0
            and s1["restored_pages"] > 0 and s1["store_errors"] == 0
            and s1["prefill_tokens"] < s0["prefill_tokens"],
            f"turn 1 on chip 0 offloaded {s0['offloaded_pages']} pages; "
            f"turn 2 on chip 1 hit {s1['prefix_hit_pages']}, restored "
            f"{s1['restored_pages']}, prefilled {s1['prefill_tokens']} "
            f"tokens against {s0['prefill_tokens']}",
        )
        smoke.check(
            engines[1].store.restored_on == {chip1}
            and set(engines[1].k_pages.devices()) == {chip1}
            and set(engines[0].k_pages.devices()) == {jax.devices()[0]},
            f"restores and pool of the second engine are on {chip1}",
        )
        print("  bytes_in_use per chip before/after turn 2: "
              f"{before} / {chip_bytes(jax)}", flush=True)
    finally:
        for w in webs:
            w.shutdown()
        for c in conns:
            c.close()


def tp_kernel_and_ici(cfg, conn, smoke, interpret):
    """(c) The shard_map'd decode kernel and the ICI page pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from infinistore_tpu.ops import paged_attention as ref
    from infinistore_tpu.ops.pallas_paged_attention import decode_attention_tp
    from infinistore_tpu.parallel.ici_handoff import IciKVPool, make_pool_mesh
    from infinistore_tpu.tpu import TpuKVStore

    devices = jax.devices()[:N_CHIPS]
    H, KV, hd, page = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.page_size
    batch, max_pages = 8, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    rng = np.random.default_rng(9)
    q = jax.random.normal(ks[0], (batch, H, hd), cfg.jdtype)
    k = jax.random.normal(ks[1], (batch * max_pages, page, KV, hd), cfg.jdtype)
    v = jax.random.normal(ks[2], (batch * max_pages, page, KV, hd), cfg.jdtype)
    table = jnp.asarray(rng.permutation(batch * max_pages)
                        .reshape(batch, max_pages), jnp.int32)
    lens = jnp.asarray(rng.integers(1, max_pages * page, batch), jnp.int32)
    out = decode_attention_tp(Mesh(np.array(devices), ("tp",)), q, k, v,
                              table, lens, interpret=interpret)
    want = ref.paged_decode_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), table, lens
    )
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    smoke.check(err < smoke.KERNEL_TOL and len(out.sharding.device_set) == 4,
                f"decode_attention_tp over {len(devices)} chips: max err "
                f"{err:.2e}, output on {len(out.sharding.device_set)} chips")

    store = TpuKVStore(conn)
    pool = IciKVPool(make_pool_mesh(N_CHIPS, devices=devices),
                     page_shape=cfg.kv_page_shape(), dtype=cfg.jdtype,
                     slots_per_device=8)
    keys = [f"multichip/ici/{i}" for i in range(4)]
    pages = np.asarray(rng.standard_normal((4, *cfg.kv_page_shape())),
                       dtype=cfg.jdtype)
    store.put_kv_pages(keys, pages, sync=True)
    fetched = pool.fetch_from_store(store, keys, device=0)
    pool.handoff({key: N_CHIPS - 1 for key in keys})
    back = np.asarray(pool.get(keys))
    smoke.check(
        fetched == 4 and all(pool.device_of(key) == N_CHIPS - 1
                             for key in keys)
        and np.array_equal(back.view(np.uint16), pages.view(np.uint16)),
        f"IciKVPool: {fetched} pages store -> chip 0 -> chip "
        f"{N_CHIPS - 1} over ICI, bit-exact",
    )


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy width on virtual CPU devices; not a chip run")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={N_CHIPS}"
        )

    import jax

    sys.path.insert(0, ROOT)
    import chip_smoke as smoke

    devs = jax.devices()
    print(f"chip_multichip: platform={devs[0].platform} "
          f"kind={devs[0].device_kind!r} count={len(devs)}", flush=True)
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if jax.default_backend() != want or len(devs) < N_CHIPS:
        print(f"chip_multichip: needs {N_CHIPS} {want} devices",
              file=sys.stderr)
        return 2
    size = smoke.TOY if args.cpu_rehearsal else smoke.CHIP
    failed = []
    try:
        smoke.build_native(clean=not args.cpu_rehearsal)
        from infinistore_tpu import ClientConfig, InfinityConnection
        from infinistore_tpu.models import llama
        from infinistore_tpu.tpu import enable_compile_cache

        enable_compile_cache()
        cfg = llama.LlamaConfig(**size["model"])
        with tempfile.TemporaryDirectory(prefix="chip_multichip_") as tmp:
            child = smoke.StoreChild(
                size["pool_gb"], cfg.kv_page_bytes() >> 10, tmp
            )
            conn = None
            try:
                conn = InfinityConnection(ClientConfig(
                    host_addr="127.0.0.1", service_port=child.service_port
                ))
                conn.connect()
                params = llama.init_params(jax.random.PRNGKey(0), cfg)
                # Every part reports, whatever the one before it did.
                for title, part in (
                    ("(a) tp=4 engine", lambda: tp_engine(
                        size, cfg, params, conn, smoke)),
                    ("(b) two one-chip engines, one store",
                     lambda: two_replicas(
                         size, cfg, params, child.service_port, smoke)),
                    ("(c) tp kernel and ICI pool",
                     lambda: tp_kernel_and_ici(
                         cfg, conn, smoke, interpret=args.cpu_rehearsal)),
                ):
                    print(f"{title}:", flush=True)
                    try:
                        part()
                    except Exception:
                        failed.append(title)
                        traceback.print_exc()
            finally:
                if conn is not None:
                    conn.close()
                child.stop()
    except smoke.SmokeFailure as e:
        failed.append(str(e))
    if failed:
        print(f"chip_multichip: FAILED: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "platform": devs[0].platform,
                      "count": len(devs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
