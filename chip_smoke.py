#!/usr/bin/env python3
"""Chip smoke: the store -> engine path, once, on one TPU chip.

    python chip_smoke.py                  # needs a TPU; fails without one
    python chip_smoke.py --cpu-rehearsal  # toy width on the CPU backend

One process holds the chip. It rebuilds the native library from the
sources beside it, starts the store through its CLI as a JAX-free child,
builds Llama-3.2-1B at its published widths with seeded random weights,
and serves two turns of eight prefix-sharing conversations over HTTP
through ServingHTTPServer -> ServingEngine -> TpuKVStore (SHM path).
Every phase asserts; the first miss exits non-zero. The last line of
stdout is {"ok": true, "device": {...}} with the device as JAX reports
it. Compile time, compile counts, wall times and peak memory are printed
as set-up facts, not as metrics.
"""

import argparse
import concurrent.futures
import importlib.metadata
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# meta-llama/Llama-3.2-1B config.json: hidden 2048, 16 layers, 32 query /
# 8 KV heads (head_dim 64), intermediate 8192, vocab 128256, rope theta
# 500000 with llama3 scaling (factor 32, low 1, high 4, original 8192),
# bf16. The checkpoint ties lm_head to the embedding; init_params keeps
# them apart, which random weights cannot tell.
CHIP = {
    "model": dict(
        vocab_size=128256, d_model=2048, n_layers=16, n_heads=32,
        n_kv_heads=8, d_ff=8192, max_seq=131072, page_size=16,
        rope_theta=500000.0, rope_scaling=(32.0, 1.0, 4.0, 8192.0),
        dtype="bfloat16",
    ),
    "serving": dict(max_slots=8, total_pages=2048, max_pages_per_seq=128),
    "pool_gb": 1.0,
    # Tails 80 tokens apart: a turn-2 prompt (turn 1 + 32 answered + 48
    # new = +80) pads to a cold-prefill length turn 1 already compiled.
    "prefix": 512, "tails": (128, 208, 288), "new": 32, "extra": 48,
    "verify_m": (5, 128), "window": 64, "honesty_pages": 2048,
}
TOY = {
    "model": dict(
        vocab_size=256, d_model=512, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=512, max_seq=512, page_size=16, rope_theta=500000.0,
        rope_scaling=(32.0, 1.0, 4.0, 8192.0), dtype="bfloat16",
    ),
    "serving": dict(max_slots=8, total_pages=256, max_pages_per_seq=16),
    "pool_gb": 0.125,
    "prefix": 64, "tails": (16, 48, 80), "new": 8, "extra": 24,
    "verify_m": (5,), "window": 24, "honesty_pages": 64,
}
# bf16 outputs against float32 references: the bound the repo's kernel
# tests use (tests/test_pallas_paged.py, tests/test_flash_prefill.py).
KERNEL_TOL = 3e-2
# Hit-path against store-less first-token logits: both run 16 layers of
# bf16 matmuls over differently tiled attention; logits are ~N(0, 1).
LOGIT_TOL = 0.25


class SmokeFailure(Exception):
    pass


def check(cond, what):
    print(f"  {'ok  ' if cond else 'FAIL'} {what}", flush=True)
    if not cond:
        raise SmokeFailure(what)


def build_native(clean):
    for tool in ("make", "g++"):
        if shutil.which(tool) is None:
            raise SmokeFailure(f"cannot build native/: no {tool} on PATH")
    native = os.path.join(ROOT, "native")
    if not os.path.exists(os.path.join(native, "Makefile")):
        raise SmokeFailure(f"no native/Makefile beside {__file__}")
    t0 = time.perf_counter()
    cmds = [["make", "-C", native, f"-j{os.cpu_count() or 1}", "all"]]
    if clean:  # separate invocations: -j would race clean against all
        cmds.insert(0, ["make", "-C", native, "clean"])
    for cmd in cmds:
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            raise SmokeFailure(
                f"{' '.join(cmd)} failed:\n{r.stderr[-4000:]}"
            )
    print(f"native: built in {time.perf_counter() - t0:.1f}s "
          f"({'clean' if clean else 'incremental'})", flush=True)


class StoreChild:
    """The store through its CLI, as a child that never imports JAX."""

    def __init__(self, pool_gb, block_kb, tmp):
        port_file = os.path.join(tmp, "ports.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # belt and braces
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "infinistore_tpu.server",
             "--host", "127.0.0.1", "--service-port", "0",
             "--manage-port", "0", "--port-file", port_file,
             "--prealloc-size", str(pool_gb),
             "--minimal-allocate-size", str(block_kb)],
            cwd=ROOT, env=env,
        )
        try:
            deadline = time.monotonic() + 120
            while not os.path.exists(port_file):
                if self.proc.poll() is not None:
                    raise SmokeFailure(
                        f"store exited with {self.proc.returncode} at "
                        f"start-up"
                    )
                if time.monotonic() > deadline:
                    raise SmokeFailure("store did not come up in 120s")
                time.sleep(0.05)
            with open(port_file) as f:
                ports = json.load(f)
            self.service_port = ports["service_port"]
            self.manage_port = ports["manage_port"]
            health = get_json(f"http://127.0.0.1:{self.manage_port}/health")
            with open(f"/proc/{self.proc.pid}/maps") as f:
                maps = f.read()
            check(health.get("status") == "ok"
                  and "jaxlib" not in maps and "libtpu" not in maps,
                  f"store child up (/health {health.get('status')}), "
                  f"no jaxlib or libtpu mapped in it")
        except BaseException:
            self.proc.kill()
            self.proc.wait()
            raise

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        leaked = [n for n in os.listdir("/dev/shm")
                  if n.startswith(f"istpu_{self.proc.pid}_")]
        check(self.proc.returncode == 0 and not leaked,
              f"store stopped: rc={self.proc.returncode}, "
              f"/dev/shm leftovers={leaked}")


class CompileMeter:
    """Counts XLA executable builds (persistent-cache hits included) and
    the seconds they took, from JAX's own monitoring events."""

    def __init__(self, jax):
        self.n = 0
        self.secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check_kernels(cfg, size, interpret):
    """Every attention kernel serving.py can dispatch, at the model's
    head geometry, against the XLA functions in ops/paged_attention.py
    evaluated in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.ops import kv_quant, paged_attention as ref
    from infinistore_tpu.ops import pallas_flash_attention as flash
    from infinistore_tpu.ops import pallas_paged_attention as paged

    f32 = jnp.float32
    H, KV, hd, page = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.page_size
    batch, max_pages = 8, 16
    n_pages = batch * max_pages
    m_max = max(size["verify_m"])
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    rng = np.random.default_rng(7)
    k_pages = jax.random.normal(ks[0], (n_pages, page, KV, hd), cfg.jdtype)
    v_pages = jax.random.normal(ks[1], (n_pages, page, KV, hd), cfg.jdtype)
    table = jnp.asarray(
        rng.permutation(n_pages).reshape(batch, max_pages), jnp.int32
    )
    lens = jnp.asarray(
        rng.integers(1, max_pages * page - m_max, batch), jnp.int32
    )

    def err(got, want):
        return float(jnp.max(jnp.abs(got.astype(f32) - want)))

    def up(*xs):
        return [x.astype(f32) for x in xs]

    k_q, k_s = kv_quant.quantize_kv_pages(k_pages)
    v_q, v_s = kv_quant.quantize_kv_pages(v_pages)
    k_dq = kv_quant.dequantize_kv_pages(k_q, k_s, f32)
    v_dq = kv_quant.dequantize_kv_pages(v_q, v_s, f32)
    for window in (0, size["window"]):
        q = jax.random.normal(ks[2], (batch, H, hd), cfg.jdtype)
        e = err(
            paged.paged_flash_decode(q, k_pages, v_pages, table, lens,
                                     interpret=interpret, window=window),
            ref.paged_decode_attention(*up(q, k_pages, v_pages), table,
                                       lens, window=window),
        )
        check(e < KERNEL_TOL, f"paged_flash_decode window={window}: "
                              f"max err {e:.2e}")
        e = err(
            paged.paged_flash_decode_quantized(
                q, k_q, k_s, v_q, v_s, table, lens, interpret=interpret,
                window=window),
            ref.paged_decode_attention(q.astype(f32), k_dq, v_dq, table,
                                       lens, window=window),
        )
        check(e < KERNEL_TOL, f"paged_flash_decode_quantized "
                              f"window={window}: max err {e:.2e}")
        for m in size["verify_m"]:
            qm = jax.random.normal(ks[3], (batch, m, H, hd), cfg.jdtype)
            e = err(
                paged.paged_flash_verify(qm, k_pages, v_pages, table, lens,
                                         interpret=interpret, window=window),
                ref.multi_token_paged_attention(
                    *up(qm, k_pages, v_pages), table, lens, window=window),
            )
            check(e < KERNEL_TOL, f"paged_flash_verify m={m} "
                                  f"window={window}: max err {e:.2e}")
        # Cold prefill is square; a prefix hit is a short suffix over
        # prefix + suffix keys. Shapes are the request script's own.
        s_cold = size["prefix"] + size["tails"][0]
        s_sfx = size["new"] + size["extra"]
        for s_q, s_kv in ((s_cold, s_cold), (s_sfx, s_cold + s_sfx)):
            qf = jax.random.normal(ks[4], (1, s_q, H, hd), cfg.jdtype)
            kf = jax.random.normal(ks[5], (1, s_kv, KV, hd), cfg.jdtype)
            vf = jax.random.normal(ks[6], (1, s_kv, KV, hd), cfg.jdtype)
            e = err(
                flash.flash_prefill_attention(
                    qf, kf, vf, causal=True, interpret=interpret,
                    window=window),
                ref.prefill_attention(*up(qf, kf, vf), causal=True,
                                      window=window),
            )
            check(e < KERNEL_TOL, f"flash_prefill_attention q={s_q} "
                                  f"kv={s_kv} window={window}: "
                                  f"max err {e:.2e}")


def lower_decode(params, cfg, sc):
    """The engine's decode step for a ServingConfig `sc`, lowered (not
    compiled) over `params` — arrays or ShapeDtypeStructs; arrays keep
    their shardings."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu import serving
    from infinistore_tpu.models import llama

    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, sc.total_pages, *cfg.kv_page_shape()), cfg.jdtype
    )
    slots = jax.ShapeDtypeStruct((sc.max_slots,), jnp.int32)
    rows = jax.ShapeDtypeStruct(
        (sc.max_slots, sc.max_pages_per_seq), jnp.int32
    )
    return serving._decode_fused.lower(
        params, cfg, slots, slots, pool, pool, rows, model=llama
    )


def check_mosaic(eng, params, cfg, size):
    """The decode, cold-prefill and prefix-prefill programs the engine
    dispatches must lower to the Mosaic custom call, i.e. the backend
    switches in ops/ took the kernel and not ops/paged_attention.py."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu import serving
    from infinistore_tpu.models import llama

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    sc = eng.sc
    i32 = jnp.int32
    p = jax.tree_util.tree_map(spec, params)
    pool = spec(eng.k_pages)
    s_cold = size["prefix"] + size["tails"][0]
    s_sfx = size["new"] + size["extra"]
    kv = jax.ShapeDtypeStruct(
        (1, s_cold, cfg.n_kv_heads, cfg.head_dim), cfg.jdtype
    )
    programs = {
        "decode": lower_decode(p, cfg, sc),
        "cold prefill": serving._admit_fused.lower(
            p, cfg, jax.ShapeDtypeStruct((1, s_cold), i32), pool, pool,
            jax.ShapeDtypeStruct((sc.max_pages_per_seq,), i32),
            jax.ShapeDtypeStruct((), i32), model=llama),
        "prefix prefill": serving._prefill_px_jit.lower(
            p, cfg, jax.ShapeDtypeStruct((1, s_sfx), i32),
            [(kv, kv)] * cfg.n_layers, jax.ShapeDtypeStruct((), i32),
            model=llama),
    }
    for name, lowered in programs.items():
        check("tpu_custom_call" in lowered.as_text(),
              f"{name} program lowers to the Mosaic custom call")


def post_json(url, body, timeout):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST"
    )
    return json.load(urllib.request.urlopen(req, timeout=timeout))


def get_json(url):
    return json.load(urllib.request.urlopen(url, timeout=30))


def run_turn(base, prompts, new_tokens):
    """POST every prompt at once from threads; returns the token lists
    in prompt order."""
    with concurrent.futures.ThreadPoolExecutor(len(prompts)) as ex:
        futs = [
            ex.submit(post_json, f"{base}/generate",
                      {"prompt": p, "max_new_tokens": new_tokens,
                       "stream": False}, 1100)
            for p in prompts
        ]
        return [f.result()["tokens"] for f in futs]


def conversations(size, vocab, seed):
    """Eight conversations sharing one system prefix, with seeded tails
    on three page-multiple lengths (cold admission compiles per padded
    length)."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def toks(n):
        return [int(t) for t in rng.integers(0, vocab, n)]

    prefix = toks(size["prefix"])
    tails = [size["tails"][i % len(size["tails"])] for i in range(8)]
    return [prefix + toks(n) for n in tails], [toks(size["extra"])
                                                for _ in tails]


def two_turns(base, size, vocab, seed, between=None):
    """Turn 1, then the same conversations extended by their answers
    plus new tokens. Returns per-turn engine-counter deltas."""

    def turn(n, prompts):
        out = run_turn(base, prompts, size["new"])
        s = get_json(f"{base}/stats")
        # A failed device step is swallowed by the engine loop: clients
        # get [] and only /stats says the engine went down.
        check(s["engine_ok"] and s["engine"]["store_errors"] == 0
              and all(len(o) == size["new"] for o in out),
              f"turn {n}: {len(out)} responses of {size['new']} tokens, "
              f"engine_ok={s['engine_ok']}, "
              f"store_errors={s['engine']['store_errors']}")
        return out, s["engine"]

    prompts, extras = conversations(size, vocab, seed)
    s0 = get_json(f"{base}/stats")["engine"]
    out1, s1 = turn(1, prompts)
    turn2 = [p + o + x for p, o, x in zip(prompts, out1, extras)]
    if between is not None:
        between(turn2)
    _, s2 = turn(2, turn2)
    return ({k: s1[k] - s0[k] for k in s1}, {k: s2[k] - s1[k] for k in s2})


def check_logits(eng, store, params, cfg, prompt):
    """First-token logits of a hit-path admission of `prompt` against a
    store-less admission of the same prompt, through the programs the
    engine dispatches (the engine is idle while this runs)."""
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu import serving
    from infinistore_tpu.models import llama

    page = cfg.page_size
    work = serving._Work(
        req=serving.Request("logit-check", prompt), prompt=list(prompt)
    )
    hit, digests = eng._probe_hit(work)
    check(hit > 0, f"probe of a turn-2 prompt hits {hit} pages")
    kp, vp = llama.restore_prefix_pages(
        store, cfg,
        lambda li, kind: serving.content_page_keys(
            prompt, page, hit, li, kind, digests=digests),
        hit,
    )
    prefix_kvs = [
        llama.pages_to_kv(cfg, kp[li][None], vp[li][None], hit * page)
        for li in range(cfg.n_layers)
    ]

    def padded(tokens):
        out = np.zeros((1, -(-len(tokens) // page) * page), np.int32)
        out[0, :len(tokens)] = tokens
        return jnp.asarray(out)

    suffix = prompt[hit * page:]
    logits, _ = serving._prefill_px_jit(
        params, cfg, padded(suffix), prefix_kvs, np.int32(0), model=llama
    )
    row_hit = np.asarray(logits[0, len(suffix) - 1])
    # Store-less: the cold-admission program with every page id at the
    # drop sentinel, so the (donated) pool comes back unchanged.
    drop = jnp.full(eng.sc.max_pages_per_seq, eng.sc.total_pages, jnp.int32)
    row, eng.k_pages, eng.v_pages = serving._admit_fused(
        params, cfg, padded(prompt), eng.k_pages, eng.v_pages, drop,
        np.int32(len(prompt)), model=llama,
    )
    row_cold = np.asarray(row)
    err = float(np.max(np.abs(row_hit - row_cold)))
    check(np.isfinite(row_hit).all() and row_hit.shape == (cfg.vocab_size,)
          and err < LOGIT_TOL,
          f"hit-path vs store-less first-token logits: max |diff| "
          f"{err:.3f} < {LOGIT_TOL} (max |logit| "
          f"{float(np.max(np.abs(row_cold))):.2f}, argmax "
          f"{'same' if row_hit.argmax() == row_cold.argmax() else 'differs'})")


def check_h2d_honest(conn, store, cfg, n_pages):
    """tpu.py releases a pin lease the moment its device_put from the
    pinned SHM pool returns, so the bytes must have left the pool by
    then: overwrite the source after it returns; the device copy must
    not change."""
    import numpy as np

    from infinistore_tpu import tpu

    rng = np.random.default_rng(3)
    shape = cfg.kv_page_shape()
    pages = rng.integers(0, 1 << 16, (n_pages, *shape), dtype=np.uint16)
    keys = [f"smoke/h2d/{i}" for i in range(n_pages)]
    store.put_kv_pages(keys, pages, sync=True)
    lease, blocks = conn.pin(keys)
    try:
        view = store._pool_batch_view(
            blocks, n_pages, pages[0].nbytes, np.uint16, shape
        )
        zero_copy = view.base is not None  # one run of pool blocks
        dev = tpu._device_put_owned(view, None)
        if zero_copy:
            view[...] = 0
    finally:
        conn.release(lease)
        conn.delete_keys(keys)
    check(zero_copy and np.array_equal(np.asarray(dev), pages),
          f"device_put of {pages.nbytes >> 10} KiB from the pinned pool "
          f"is complete when it returns (source overwritten after)")
    # A read across put batches is copied once, run by run, into the
    # store's staging buffer, which the NEXT such read reuses: the
    # device copy of the first must not change under the second.
    halves = [keys[:n_pages // 2], keys[n_pages // 2:]]
    for ks, ps in zip(halves, np.split(pages, [n_pages // 2])):
        store.put_kv_pages(ks, ps, sync=True)
        store.put_kv_pages([ks[0] + "/spacer"], ps[:1], sync=True)
    crossed = halves[1] + halves[0]
    try:
        first = store.get_kv_pages(crossed, shape, np.uint16)
        runs = store.last_read["runs"]
        store.get_kv_pages(crossed[::-1], shape, np.uint16)
    finally:
        conn.delete_keys(keys + [h[0] + "/spacer" for h in halves])
    want = np.concatenate([pages[n_pages // 2:], pages[:n_pages // 2]])
    check(runs >= 2 and np.array_equal(np.asarray(first), want),
          f"a read of {runs} runs of the pool, staged and transferred, "
          f"equals the pages written (staging reused after)")


def check_offload_ownership(eng, conn, cfg):
    """serving.py frees a finished slot's pool pages as soon as the
    gathers over them are dispatched, and the engine's upload thread
    writes them to the store later (`_offload_full_pages` has the
    rule). Hold the upload thread before its first wait for a transfer,
    finish a slot over a whole page table of random rows, overwrite
    every one of its pool pages by a program dispatched behind the
    gathers (what the next admission's scatter does), then let the
    upload go: the store must hold the rows as they were."""
    import threading

    import jax
    import numpy as np

    from infinistore_tpu import serving

    n, L = eng.sc.max_pages_per_seq, cfg.n_kv_layers
    shape = (L, n, *cfg.kv_page_shape())
    kk, kv = jax.random.split(jax.random.PRNGKey(5))
    k_new = jax.random.normal(kk, shape, cfg.jdtype)
    v_new = jax.random.normal(kv, shape, cfg.jdtype)
    ids = eng._alloc(n)[::-1]
    at = jax.numpy.asarray(ids)
    # in place (the pools donated), as an admission's scatter writes
    write = jax.jit(lambda k, v, k_new, v_new: (
        k.at[:, at].set(k_new), v.at[:, at].set(v_new)),
        donate_argnums=(0, 1))
    eng.k_pages, eng.v_pages = write(eng.k_pages, eng.v_pages, k_new, v_new)
    # page-major (page, layer, k then v): the order of an offload's rows
    want = np.swapaxes(np.stack([np.asarray(k_new), np.asarray(v_new)],
                                axis=2), 0, 1)
    prompt = [int(t) for t in np.random.default_rng(5).integers(
        0, cfg.vocab_size, n * cfg.page_size)]
    slot = serving._Slot(
        work=serving._Work(req=serving.Request("smoke-own", prompt,
                                               max_new_tokens=1),
                           prompt=prompt),
        page_ids=ids, seq_len=len(prompt))
    eng.slots[0] = slot
    gate, real = threading.Event(), serving.to_host

    def held(arr):
        gate.wait()
        return real(arr)
    serving.to_host = held
    try:
        eng._finish(0, slot)
        freed = set(ids) <= set(eng.free_pages)
        eng.k_pages, eng.v_pages = write(
            eng.k_pages, eng.v_pages, jax.numpy.zeros_like(k_new),
            jax.numpy.zeros_like(v_new))
        jax.block_until_ready((eng.k_pages, eng.v_pages))
        held_back = eng.collect_uploads() == 0 and not eng.outputs
    finally:
        gate.set()
        serving.to_host = real
    eng.drain_uploads()
    keys = serving.content_page_keys_by_page(eng._digests(prompt, n), L)
    back = eng.store.get_kv_pages_host(keys, cfg.kv_page_shape(),
                                       cfg.jdtype)
    conn.delete_keys(keys)
    check(freed and held_back and eng.outputs.pop("smoke-own") == []
          and eng.stats["store_errors"] == 0
          and np.array_equal(np.asarray(back).view(np.uint16),
                             want.reshape(back.shape).view(np.uint16)),
          f"{n} pages ({want.nbytes >> 20} MiB) freed and overwritten "
          f"behind their gathers reach the store as they were; `done` "
          f"waited for the upload thread's sync")


def check_stream_roundtrip(service_port, cfg):
    """A few pages device -> store -> device over the STREAM (TCP) path,
    bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu import TYPE_STREAM, ClientConfig, InfinityConnection
    from infinistore_tpu.tpu import TpuKVStore

    conn = InfinityConnection(ClientConfig(
        host_addr="127.0.0.1", service_port=service_port,
        connection_type=TYPE_STREAM,
    ))
    conn.connect()
    try:
        store = TpuKVStore(conn)
        pages = jax.random.normal(
            jax.random.PRNGKey(11), (4, *cfg.kv_page_shape()), cfg.jdtype
        )
        keys = [f"smoke/stream/{i}" for i in range(4)]
        store.put_kv_pages(keys, pages, sync=True)
        back = store.get_kv_pages(keys, cfg.kv_page_shape(), cfg.jdtype)
        same = bool(jnp.array_equal(
            jax.lax.bitcast_convert_type(back, jnp.uint16),
            jax.lax.bitcast_convert_type(pages, jnp.uint16),
        ))
        check(not conn.shm_connected and same
              and next(iter(back.devices())) == jax.devices()[0],
              "STREAM put/get of 4 pages, device to device, bit-exact")
    finally:
        conn.close()


def run(size, rehearsal, tmp):
    import jax
    import numpy as np

    from infinistore_tpu import ClientConfig, InfinityConnection
    from infinistore_tpu.models import llama
    from infinistore_tpu.serving import ServingConfig, ServingEngine
    from infinistore_tpu.serving_http import ServingHTTPServer
    from infinistore_tpu.tpu import TpuKVStore, enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter(jax)
    cfg = llama.LlamaConfig(**size["model"])

    print("kernels against the XLA reference:", flush=True)
    check_kernels(cfg, size, interpret=rehearsal)

    class TappedStore(TpuKVStore):
        """Keeps the first offloaded batch — its keys and the device
        array the engine gathered from the HBM pool — for the
        read-back check."""
        tapped = None

        def put_kv_pages(self, keys, pages, sync=False):
            if self.tapped is None:
                self.tapped = (list(keys), pages)
            return super().put_kv_pages(keys, pages, sync=sync)

    # Pool blocks of one KV page each: a batch of pages is then one
    # contiguous run of the pool, which device_put reads in place.
    child = StoreChild(size["pool_gb"], cfg.kv_page_bytes() >> 10, tmp)
    conn = web = None
    try:
        conn = InfinityConnection(ClientConfig(
            host_addr="127.0.0.1", service_port=child.service_port
        ))
        conn.connect()
        check(conn.shm_connected, "engine's store connection is SHM")
        store = TappedStore(conn)
        print("store edge:", flush=True)
        # First, on a fresh pool: first-fit hands one batch contiguous
        # blocks, so the honesty check gets a zero-copy pool view.
        check_h2d_honest(conn, TpuKVStore(conn), cfg, size["honesty_pages"])
        check_stream_roundtrip(child.service_port, cfg)

        t0 = time.perf_counter()
        params = jax.block_until_ready(
            llama.init_params(jax.random.PRNGKey(0), cfg)
        )
        print(f"model: {llama.param_bytes(params) / 1e9:.2f} GB of "
              f"weights, {cfg.n_layers} layers, seed 0, "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        eng = ServingEngine(
            params, cfg, ServingConfig(**size["serving"]), store=store
        )
        params = eng.params  # committed to the engine's device
        print("offload:", flush=True)
        check_offload_ownership(eng, conn, cfg)
        store.tapped = None  # the first batch a TURN offloads is kept
        web = ServingHTTPServer(eng)
        base = f"http://127.0.0.1:{web.start()}"

        def between_turns(turn2_prompts):
            keys, dev_pages = store.tapped
            back = store.get_kv_pages_host(
                keys, cfg.kv_page_shape(), cfg.jdtype
            )
            check(np.array_equal(back.view(np.uint16),
                                 np.asarray(dev_pages).view(np.uint16)),
                  f"{len(keys)} offloaded pages read back from the store "
                  f"equal the HBM pool's copy bit for bit")
            check_logits(eng, store, params, cfg, turn2_prompts[0])

        print("cold pass, two turns over HTTP:", flush=True)
        t0 = time.perf_counter()
        d1, d2 = two_turns(base, size, cfg.vocab_size, seed=1,
                           between=between_turns)
        cold_s = time.perf_counter() - t0
        check(d1["offloaded_pages"] > 0,
              f"turn 1 offloaded {d1['offloaded_pages']} pages")
        check(d2["prefix_hit_pages"] > 0 and d2["restored_pages"] > 0,
              f"turn 2 hit {d2['prefix_hit_pages']} pages, restored "
              f"{d2['restored_pages']} (layer, kind) pages")
        check(d2["prefill_tokens"] < d1["prefill_tokens"],
              f"turn 2 prefilled {d2['prefill_tokens']} tokens, turn 1 "
              f"{d1['prefill_tokens']}")
        n_cold, secs_cold = meter.n, meter.secs

        print("warm pass, same shapes, new tokens:", flush=True)
        t0 = time.perf_counter()
        two_turns(base, size, cfg.vocab_size, seed=2)
        warm_s = time.perf_counter() - t0

        if not rehearsal:
            print("lowered programs:", flush=True)
            check_mosaic(eng, params, cfg, size)

        stats = jax.devices()[0].memory_stats() or {}
        print("set-up facts (not metrics): " + json.dumps({
            "compile_s": round(secs_cold, 1),
            "compilations": n_cold,
            "persistent_cache_hits": meter.cache_hits,
            "compilations_after_warmup": meter.n - n_cold,
            "cold_wall_s": round(cold_s, 1),
            "warm_wall_s": round(warm_s, 1),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "compile_cache_dir": cache_dir,
        }), flush=True)
    finally:
        if web is not None:
            web.shutdown()
        if conn is not None:
            conn.close()
        child.stop()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="toy width on the CPU backend with interpret-mode kernels; "
             "not a chip run",
    )
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={device['count']} jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    backend = jax.default_backend()
    if args.cpu_rehearsal:
        if backend != "cpu":
            print(f"chip_smoke: --cpu-rehearsal needs the CPU backend, "
                  f"found {backend!r}", file=sys.stderr)
            return 2
        print("chip_smoke: CPU REHEARSAL at a toy width with "
              "interpret-mode kernels — NOT a chip run", flush=True)
    elif backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found backend {backend!r}",
              file=sys.stderr)
        return 2

    try:
        # The chip run builds from the copied sources, never from a
        # .so or .o that rode along; the rehearsal only builds what is
        # missing (tier-1 shares the library with other tests).
        build_native(clean=not args.cpu_rehearsal)
        sys.path.insert(0, ROOT)
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            run(TOY if args.cpu_rehearsal else CHIP, args.cpu_rehearsal, tmp)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if args.cpu_rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
