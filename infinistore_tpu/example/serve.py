"""Continuous-batching serving demo: the full engine loop over the
store — multi-turn prefix caching, admission in pieces, speculative
decoding — against a live server.

Run a server first (`python -m infinistore_tpu.server --service-port
22345 ...`), then: `python -m infinistore_tpu.example.serve
--service-port 22345`.

What it shows, in order:
1. Turn 1: a batch of requests is served with continuous batching;
   finished sequences offload their KV pages to the store.
2. Turn 2: conversations extend their turn-1 prompts — admission HITS
   the cached pages (content-addressed keys), restores them, and
   prefills only the new tokens, a piece an engine step.
3. Speculation: a repetitive prompt decodes with prompt-lookup drafts
   accepted several-at-a-time.
4. With --http-port: the engine goes ONLINE — an HTTP front end
   (serving_http.ServingHTTPServer) serves POST /generate with
   streamed tokens and GET /stats with per-request TTFT/tok_s. Drive
   it with, e.g.:

       curl -N -XPOST localhost:8080/generate \
            -d '{"prompt": [1,2,3], "max_new_tokens": 8}'
       curl localhost:8080/stats
"""

import argparse

import jax
import numpy as np

from infinistore_tpu import ClientConfig, InfinityConnection
from infinistore_tpu.models import llama
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore, enable_compile_cache


def run(host, port, http_port=None, http_demo_requests=False):
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(jax.devices())}")
    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=128, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq=256, page_size=16,
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    conn = InfinityConnection(
        ClientConfig(host_addr=host, service_port=port)
    )
    conn.connect()
    store = TpuKVStore(conn)
    rng = np.random.default_rng(0)

    def fmt(stats):
        return {k: v for k, v in stats.items() if v}

    # -- turn 1: continuous batching + offload-on-finish --------------
    prompts = [
        [int(t) for t in rng.integers(0, cfg.vocab_size, n)]
        for n in (24, 40, 18)
    ]
    eng = ServingEngine(
        params, cfg, ServingConfig(max_slots=2), store=store
    )
    out1 = eng.run(
        [Request(f"conv{i}", p, max_new_tokens=12)
         for i, p in enumerate(prompts)]
    )
    print(f"turn 1: {len(out1)} requests through 2 slots; {fmt(eng.stats)}")

    # -- turn 2: prefix-cache HIT + admission in pieces ----------------
    eng2 = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=2, admit_piece=cfg.page_size),
        store=store,
    )
    turn2 = []
    for i, p in enumerate(prompts):
        convo = p + out1[f"conv{i}"]
        keep = (len(convo) // cfg.page_size) * cfg.page_size
        turn2.append(
            Request(
                f"conv{i}",
                convo[:keep]
                + [int(t) for t in rng.integers(0, cfg.vocab_size, 22)],
                max_new_tokens=8,
            )
        )
    eng2.run(turn2)
    hits = eng2.stats["prefix_hit_pages"]
    print(
        f"turn 2: {hits} pages/layer-batch restored from the store, "
        f"only {eng2.stats['prefill_tokens']} tokens prefilled, in "
        f"{eng2.stats['admit_pieces']} pieces; {fmt(eng2.stats)}"
    )
    assert hits > 0, "expected turn-2 prefix hits"
    assert eng2.stats["admit_pieces"] > 0, "expected admission in pieces"

    # -- speculation on a repetitive prompt ----------------------------
    block = [int(t) for t in rng.integers(0, cfg.vocab_size, 6)]
    rep = (block * 8)[:44]
    eng3 = ServingEngine(
        params, cfg, ServingConfig(spec_k=4), store=store
    )
    eng3.run([Request("rep", rep, max_new_tokens=16)])
    # Acceptance depends on whether the (random-weight) model actually
    # continues the repetition; proposals are deterministic — the
    # n-gram machinery must always have fired on this prompt.
    assert eng3.stats["spec_proposed"] > 0, "expected drafts"
    print(
        f"speculative: {eng3.stats['spec_accepted']}/"
        f"{eng3.stats['spec_proposed']} drafts accepted, "
        f"{eng3.stats['decoded_tokens']} tokens in "
        f"{eng3.stats['decode_steps']} steps"
    )
    # 4. Online serving: real requests over a real socket.
    if http_port is not None:
        from infinistore_tpu.serving_http import ServingHTTPServer

        eng4 = ServingEngine(
            params, cfg, ServingConfig(max_slots=4, total_pages=64),
            store=store,
        )
        web = ServingHTTPServer(eng4, port=http_port)
        bound = web.start()
        if http_demo_requests:
            import json as _json
            import urllib.request as _rq

            body = _json.dumps(
                {"prompt": [1, 2, 3, 4], "max_new_tokens": 8,
                 "stream": False}
            ).encode()
            res = _json.loads(
                _rq.urlopen(
                    _rq.Request(
                        f"http://127.0.0.1:{bound}/generate", data=body,
                        method="POST",
                    ),
                    timeout=60,
                ).read()
            )
            print(
                f"http: served {len(res['tokens'])} tokens, "
                f"ttft {res['ttft_ms']} ms, {res['tok_s']} tok/s"
            )
            web.shutdown()
        else:
            print(f"http: serving on :{bound} (POST /generate, /stats)")
            try:
                web._http_thread.join()
            except KeyboardInterrupt:
                web.shutdown()
    conn.close()


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--service-port", type=int, default=22345)
    p.add_argument("--http-port", type=int, default=None,
                   help="also serve the engine over HTTP on this port "
                        "(0 = ephemeral)")
    p.add_argument("--http-demo", action="store_true",
                   help="with --http-port: fire one demo request and "
                        "exit instead of serving forever")
    args = p.parse_args()
    print(f"compile cache: {enable_compile_cache()}")
    run(args.host, args.service_port, http_port=args.http_port,
        http_demo_requests=args.http_demo)
