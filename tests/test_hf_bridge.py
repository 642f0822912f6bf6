"""HF weight-bridge tests: numerical parity with `transformers`.

The strongest correctness evidence for the model family — the same
weights must produce the same logits from the canonical torch
implementation and from our JAX one (prefill AND the paged decode
path)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from infinistore_tpu.models import hf, llama  # noqa: E402


@pytest.fixture(scope="module")
def hf_model():
    cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=160,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=128,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        attention_bias=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg).eval()


def test_config_mapping(hf_model):
    cfg = hf.config_from_hf(hf_model.config, page_size=8)
    assert cfg.d_model == 64 and cfg.n_heads == 4 and cfg.n_kv_heads == 2
    assert cfg.d_ff == 160 and cfg.vocab_size == 128
    assert cfg.norm_eps == 1e-5 and cfg.page_size == 8


def test_prefill_logits_match_transformers(hf_model):
    cfg, params = hf.load_hf(hf_model, page_size=8, dtype="float32")
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 24), dtype=np.int64)

    with torch.no_grad():
        ref = hf_model(torch.from_numpy(tokens)).logits.numpy()

    ours, _ = llama.prefill(params, cfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    # float32 end to end; differences are op-ordering only.
    err = np.abs(ours - ref).max()
    assert err < 2e-4, err
    # The argmax token stream — what a generator emits — is identical.
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_paged_decode_matches_transformers(hf_model):
    """Decode through OUR paged-KV path vs transformers full forward:
    prefill N tokens, page the KV out and back (as the store would),
    then decode the next token."""
    cfg, params = hf.load_hf(hf_model, page_size=8, dtype="float32")
    rng = np.random.default_rng(1)
    seq = 16  # two full pages
    tokens = rng.integers(0, cfg.vocab_size, (1, seq + 1), dtype=np.int64)

    with torch.no_grad():
        ref = hf_model(torch.from_numpy(tokens)).logits.numpy()[0, -1]

    _, kvs = llama.prefill(
        params, cfg, jnp.asarray(tokens[:, :seq], jnp.int32)
    )
    n_pages = seq // cfg.page_size
    max_pages = n_pages + 1  # room for the decode token
    k_pages = jnp.zeros(
        (cfg.n_layers, max_pages, cfg.page_size, cfg.n_kv_heads,
         cfg.head_dim), dtype=cfg.jdtype,
    )
    v_pages = jnp.zeros_like(k_pages)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        k_pages = k_pages.at[li, :n_pages].set(kp[0])
        v_pages = v_pages.at[li, :n_pages].set(vp[0])
    page_table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    logits, _, _ = llama.decode_step(
        params, cfg,
        jnp.asarray(tokens[:, seq], jnp.int32).reshape(1),
        jnp.asarray([seq], jnp.int32),
        k_pages, v_pages, page_table,
    )
    ours = np.asarray(logits[0])
    err = np.abs(ours - ref).max()
    assert err < 2e-4, err
    assert int(ours.argmax()) == int(ref.argmax())


def test_tied_embeddings_fallback():
    """Checkpoints with tied embeddings have no lm_head.weight; the
    bridge falls back to embed.T."""
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=64,
        rms_norm_eps=1e-5, tie_word_embeddings=True,
    )
    torch.manual_seed(1)
    m = transformers.LlamaForCausalLM(cfg).eval()
    sd = {k: v for k, v in m.state_dict().items()
          if k != "lm_head.weight"}
    our_cfg = hf.config_from_hf(cfg)
    params = hf.params_from_hf(sd, our_cfg)
    np.testing.assert_array_equal(
        np.asarray(params["lm_head"]), np.asarray(params["embed"]).T
    )


@pytest.fixture(scope="module")
def hf_model_31():
    """Llama-3.1-style checkpoint: llama3 rope_scaling + attention
    biases (the Qwen2-family geometry) — the two features real served
    checkpoints carry that plain Llama-3 does not."""
    cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=160,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
        attention_bias=True,
        tie_word_embeddings=False,
    )
    torch.manual_seed(3)
    return transformers.LlamaForCausalLM(cfg).eval()


def test_rope_scaling_config_mapping(hf_model_31):
    cfg = hf.config_from_hf(hf_model_31.config, page_size=8)
    assert cfg.rope_scaling == (8.0, 1.0, 4.0, 64.0)


def test_rope_scaling_unsupported_type_raises():
    cfg = transformers.LlamaConfig(
        rope_scaling={"rope_type": "yarn", "factor": 4.0}
    )
    with pytest.raises(NotImplementedError):
        hf.config_from_hf(cfg)


def test_mlp_bias_checkpoint_raises():
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, mlp_bias=True,
    )
    torch.manual_seed(6)
    model = transformers.LlamaForCausalLM(cfg).eval()
    with pytest.raises(NotImplementedError, match="mlp_bias"):
        hf.load_hf(model, page_size=8, dtype="float32")


def test_llama31_prefill_logits_match_transformers(hf_model_31):
    """Parity BEYOND the original context window (positions > 64, where
    unscaled frequencies would diverge hard) — proves the llama3
    frequency rescale AND the q/k/v/o biases, end to end."""
    cfg, params = hf.load_hf(hf_model_31, page_size=8, dtype="float32")
    assert "bq" in params["layers"][0] and "bo" in params["layers"][0]
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, cfg.vocab_size, (2, 96), dtype=np.int64)

    with torch.no_grad():
        ref = hf_model_31(torch.from_numpy(tokens)).logits.numpy()

    ours, _ = llama.prefill(params, cfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    err = np.abs(ours - ref).max()
    assert err < 2e-4, err
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_llama31_paged_decode_matches_transformers(hf_model_31):
    """The paged decode path with scaled rope + biases: prefill, page
    out/in, decode one token past the original context window."""
    cfg, params = hf.load_hf(hf_model_31, page_size=8, dtype="float32")
    rng = np.random.default_rng(5)
    seq = 80  # ten pages, beyond original_max_position_embeddings=64
    tokens = rng.integers(0, cfg.vocab_size, (1, seq + 1), dtype=np.int64)

    with torch.no_grad():
        ref = hf_model_31(torch.from_numpy(tokens)).logits.numpy()[0, -1]

    _, kvs = llama.prefill(
        params, cfg, jnp.asarray(tokens[:, :seq], jnp.int32)
    )
    n_pages = seq // cfg.page_size
    max_pages = n_pages + 1
    k_pages = jnp.zeros(
        (cfg.n_layers, max_pages, cfg.page_size, cfg.n_kv_heads,
         cfg.head_dim), dtype=cfg.jdtype,
    )
    v_pages = jnp.zeros_like(k_pages)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        k_pages = k_pages.at[li, :n_pages].set(kp[0])
        v_pages = v_pages.at[li, :n_pages].set(vp[0])
    page_table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    logits, _, _ = llama.decode_step(
        params, cfg,
        jnp.asarray(tokens[:, seq], jnp.int32).reshape(1),
        jnp.asarray([seq], jnp.int32),
        k_pages, v_pages, page_table,
    )
    ours = np.asarray(logits[0])
    err = np.abs(ours - ref).max()
    assert err < 2e-4, err
    assert int(ours.argmax()) == int(ref.argmax())


def test_qwen2_checkpoint_loads_and_matches():
    """An actual transformers Qwen2ForCausalLM (not a biased Llama
    stand-in): same state-dict naming, q/k/v biases without o bias —
    the bridge loads it directly and matches logits."""
    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5,
        rope_theta=10000.0, use_sliding_window=False,
        tie_word_embeddings=False,
    )
    torch.manual_seed(7)
    model = transformers.Qwen2ForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    assert sorted(
        k for k in params["layers"][0] if k.startswith("b")
    ) == ["bk", "bq", "bv"]  # Qwen2: no o_proj bias

    rng = np.random.default_rng(8)
    tokens = rng.integers(0, jcfg.vocab_size, (2, 24), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = llama.prefill(params, jcfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    assert np.abs(ours - ref).max() < 2e-4
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_qwen2_swa_flag_without_width_is_full_attention():
    # transformers gates SWA on sliding_window being set; the flag
    # alone must not activate (or crash) the band.
    cfg = transformers.Qwen2Config(
        num_hidden_layers=4, use_sliding_window=True,
        sliding_window=None, max_window_layers=0,
    )
    assert hf.config_from_hf(cfg).window == 0


def test_qwen2_all_swa_layers_maps_window():
    cfg = transformers.Qwen2Config(
        num_hidden_layers=4, use_sliding_window=True,
        sliding_window=64, max_window_layers=0,
    )
    assert hf.config_from_hf(cfg).window == 64
    # max_window_layers >= n_layers: every layer keeps full attention.
    cfg2 = transformers.Qwen2Config(
        num_hidden_layers=4, use_sliding_window=True,
        sliding_window=64, max_window_layers=4,
    )
    assert hf.config_from_hf(cfg2).window == 0


def test_explicit_head_dim_loads_and_matches():
    """Decoupled head_dim (Mistral-NeMo style): head_dim=32 with
    hidden_size//heads=16 — projection shapes and the attention scale
    follow the checkpoint."""
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=32,
        max_position_embeddings=128, tie_word_embeddings=False,
    )
    torch.manual_seed(55)
    model = transformers.LlamaForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    assert jcfg.head_dim == 32
    rng = np.random.default_rng(56)
    tokens = rng.integers(0, 128, (2, 24), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = llama.prefill(params, jcfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    assert np.abs(ours - ref).max() < 2e-4
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_mistral_checkpoint_loads_and_matches():
    """MistralForCausalLM with the window disabled is llama-geometry;
    the bridge loads it directly and matches logits."""
    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5,
        rope_theta=10000.0, sliding_window=None, tie_word_embeddings=False,
    )
    torch.manual_seed(11)
    model = transformers.MistralForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    rng = np.random.default_rng(12)
    tokens = rng.integers(0, jcfg.vocab_size, (2, 24), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = llama.prefill(params, jcfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    assert np.abs(ours - ref).max() < 2e-4
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_mistral_sliding_window_prefill_matches_transformers():
    """A REAL windowed Mistral (sliding_window < seq): the JAX model's
    banded attention must match transformers' SWA masks exactly."""
    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5,
        rope_theta=10000.0, sliding_window=16, tie_word_embeddings=False,
    )
    torch.manual_seed(21)
    model = transformers.MistralForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    assert jcfg.window == 16
    rng = np.random.default_rng(22)
    tokens = rng.integers(0, jcfg.vocab_size, (2, 48), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = llama.prefill(params, jcfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    assert np.abs(ours - ref).max() < 2e-4, np.abs(ours - ref).max()
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_mistral_sliding_window_paged_decode_matches_transformers():
    """Windowed paged decode: prefill 40 tokens (2.5 windows), page the
    KV out/in, decode token 41 — band floor well inside the cache."""
    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5,
        rope_theta=10000.0, sliding_window=16, tie_word_embeddings=False,
    )
    torch.manual_seed(23)
    model = transformers.MistralForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    rng = np.random.default_rng(24)
    seq = 40
    tokens = rng.integers(0, jcfg.vocab_size, (1, seq + 1), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()[0, -1]
    _, kvs = llama.prefill(
        params, jcfg, jnp.asarray(tokens[:, :seq], jnp.int32)
    )
    n_pages = seq // jcfg.page_size
    max_pages = n_pages + 1
    k_pages = jnp.zeros(
        (jcfg.n_layers, max_pages, jcfg.page_size, jcfg.n_kv_heads,
         jcfg.head_dim), dtype=jcfg.jdtype,
    )
    v_pages = jnp.zeros_like(k_pages)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(jcfg, k, v)
        k_pages = k_pages.at[li, :n_pages].set(kp[0])
        v_pages = v_pages.at[li, :n_pages].set(vp[0])
    page_table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    logits, _, _ = llama.decode_step(
        params, jcfg,
        jnp.asarray(tokens[:, seq], jnp.int32).reshape(1),
        jnp.asarray([seq], jnp.int32),
        k_pages, v_pages, page_table,
    )
    ours = np.asarray(logits[0])
    assert np.abs(ours - ref).max() < 2e-4, np.abs(ours - ref).max()
    assert int(ours.argmax()) == int(ref.argmax())


def test_qwen2_mixed_window_layers_map_onto_the_per_layer_spec():
    """max_window_layers bottom layers keep full attention, the others
    the band: the per-layer spec (LlamaConfig.layer_bands), which the
    serving engine holds as two kinds of page. Logits match
    transformers with the band genuinely active (24 > 8)."""
    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, rms_norm_eps=1e-5, rope_theta=10000.0,
        use_sliding_window=True, sliding_window=8, max_window_layers=2,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    jcfg = hf.config_from_hf(cfg, page_size=8)
    assert jcfg.window == 0 and jcfg.layer_windows == (0, 0, 8, 8)
    assert jcfg.two_kinds and jcfg.window_band == 8
    torch.manual_seed(41)
    model = transformers.Qwen2ForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    tokens = np.random.default_rng(42).integers(0, 128, (1, 24),
                                                dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = llama.prefill(params, jcfg, jnp.asarray(tokens, jnp.int32))
    assert np.abs(np.asarray(ours) - ref).max() < 2e-4


def test_windowed_mistral_serves_through_engine():
    """End-to-end: a sliding-window checkpoint generates through the
    real ServingEngine (admission prefill + fused paged decode, both
    windowed)."""
    from infinistore_tpu.serving import Request, ServingConfig, ServingEngine

    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, sliding_window=16,
    )
    torch.manual_seed(25)
    jcfg, params = hf.load_hf(
        transformers.MistralForCausalLM(cfg).eval(), page_size=8,
        dtype="float32",
    )
    eng = ServingEngine(params, jcfg, ServingConfig(
        max_slots=2, total_pages=32, max_pages_per_seq=12))
    toks = []
    eng.submit(Request("w1", list(range(24)), max_new_tokens=6,
                       on_token=lambda r, t: toks.append(int(t))))
    eng.run([])
    assert len(toks) == 6

    # The engine's windowed token stream matches transformers' greedy
    # continuation (window genuinely active: prompt 24 > window 16).
    ids = torch.arange(24)[None]
    with torch.no_grad():
        torch.manual_seed(25)  # same weights load_hf consumed
        model = transformers.MistralForCausalLM(cfg).eval()
        out = model.generate(ids, max_new_tokens=6, do_sample=False)
    assert toks == [int(t) for t in out[0, 24:]]


def test_gemma_checkpoint_loads_and_matches():
    """GemmaForCausalLM: MQA (n_kv=1), decoupled head_dim, GeGLU,
    zero-centered (1+w) RMSNorm, sqrt(d_model)-scaled embeddings, tied
    head — the bridge maps every convention and matches logits."""
    cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=32, max_position_embeddings=128, rms_norm_eps=1e-6,
        hidden_act="gelu_pytorch_tanh", rope_theta=10000.0,
    )
    torch.manual_seed(51)
    model = transformers.GemmaForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    assert jcfg.head_dim == 32 and jcfg.act == "gelu"
    assert jcfg.norm_plus_one and jcfg.embed_scale == 8.0
    rng = np.random.default_rng(52)
    tokens = rng.integers(0, 128, (2, 24), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = llama.prefill(params, jcfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    assert np.abs(ours - ref).max() < 2e-4
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_gemma_paged_decode_matches_transformers():
    """Gemma through the paged decode path (page out/in, one decode
    step) — MQA + decoupled head_dim flow through the pool layout."""
    cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=32, max_position_embeddings=128, rms_norm_eps=1e-6,
        hidden_act="gelu_pytorch_tanh",
    )
    torch.manual_seed(53)
    model = transformers.GemmaForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    rng = np.random.default_rng(54)
    seq = 16
    tokens = rng.integers(0, 128, (1, seq + 1), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()[0, -1]
    _, kvs = llama.prefill(
        params, jcfg, jnp.asarray(tokens[:, :seq], jnp.int32)
    )
    n_pages = seq // jcfg.page_size
    max_pages = n_pages + 1
    k_pages = jnp.zeros(
        (jcfg.n_layers, max_pages, jcfg.page_size, jcfg.n_kv_heads,
         jcfg.head_dim), dtype=jcfg.jdtype,
    )
    v_pages = jnp.zeros_like(k_pages)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(jcfg, k, v)
        k_pages = k_pages.at[li, :n_pages].set(kp[0])
        v_pages = v_pages.at[li, :n_pages].set(vp[0])
    page_table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    logits, _, _ = llama.decode_step(
        params, jcfg,
        jnp.asarray(tokens[:, seq], jnp.int32).reshape(1),
        jnp.asarray([seq], jnp.int32),
        k_pages, v_pages, page_table,
    )
    ours = np.asarray(logits[0])
    assert np.abs(ours - ref).max() < 2e-4
    assert int(ours.argmax()) == int(ref.argmax())


def test_exact_gelu_checkpoint_matches():
    """hidden_act="gelu" is HF's exact erf GELU, distinct from the tanh
    approximation — the bridge must map it to the erf form, not
    silently approximate."""
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, hidden_act="gelu",
        max_position_embeddings=128, tie_word_embeddings=False,
    )
    torch.manual_seed(59)
    model = transformers.LlamaForCausalLM(cfg).eval()
    jcfg, params = hf.load_hf(model, page_size=8, dtype="float32")
    assert jcfg.act == "gelu_exact"
    rng = np.random.default_rng(60)
    tokens = rng.integers(0, 128, (2, 24), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _ = llama.prefill(params, jcfg, jnp.asarray(tokens, jnp.int32))
    ours = np.asarray(ours)
    assert np.abs(ours - ref).max() < 2e-4


def _tiny_mixtral(sliding_window=None):
    cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=128, rms_norm_eps=1e-5,
        sliding_window=sliding_window, tie_word_embeddings=False,
        attn_implementation="eager",
    )
    torch.manual_seed(61)
    return transformers.MixtralForCausalLM(cfg).eval()


def test_mixtral_checkpoint_loads_and_matches():
    """MixtralForCausalLM into the MoE family: per-expert w1/w3/w2
    stack onto the E axis, router transposes, and the no-drop capacity
    (capacity_factor = E/top_k) makes GShard dense-dispatch routing
    exactly reproduce HF's top-k — logits parity to 2e-4."""
    from infinistore_tpu.models import moe

    model = _tiny_mixtral()
    jcfg, params = hf.load_hf_moe(model, page_size=8, dtype="float32")
    assert jcfg.n_experts == 4 and jcfg.top_k == 2
    assert jcfg.capacity_factor == 2.0  # E / top_k: no token dropped
    rng = np.random.default_rng(62)
    tokens = rng.integers(0, 128, (2, 24), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
    ours, _, _ = moe.forward_dense(
        params, jcfg, jnp.asarray(tokens, jnp.int32)
    )
    ours = np.asarray(ours)
    assert np.abs(ours - ref).max() < 2e-4
    assert np.array_equal(ours.argmax(-1), ref.argmax(-1))


def test_mixtral_paged_decode_matches_transformers():
    """Mixtral through the MoE paged decode path: prefill, page
    out/in, one decode step vs the HF full forward."""
    from infinistore_tpu.models import moe

    model = _tiny_mixtral()
    jcfg, params = hf.load_hf_moe(model, page_size=8, dtype="float32")
    rng = np.random.default_rng(64)
    seq = 16
    tokens = rng.integers(0, 128, (1, seq + 1), dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()[0, -1]
    _, kvs, _ = moe.forward_dense(
        params, jcfg, jnp.asarray(tokens[:, :seq], jnp.int32)
    )
    n_pages = seq // jcfg.page_size
    max_pages = n_pages + 1
    k_pages = jnp.zeros(
        (jcfg.n_layers, max_pages, jcfg.page_size, jcfg.n_kv_heads,
         jcfg.head_dim), dtype=jcfg.jdtype,
    )
    v_pages = jnp.zeros_like(k_pages)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(jcfg, k, v)
        k_pages = k_pages.at[li, :n_pages].set(kp[0])
        v_pages = v_pages.at[li, :n_pages].set(vp[0])
    page_table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    logits, _, _ = moe.decode_step(
        params, jcfg,
        jnp.asarray(tokens[:, seq], jnp.int32).reshape(1),
        jnp.asarray([seq], jnp.int32),
        k_pages, v_pages, page_table,
    )
    ours = np.asarray(logits[0])
    assert np.abs(ours - ref).max() < 2e-4
    assert int(ours.argmax()) == int(ref.argmax())


def test_gemma2_rejected():
    cfg = transformers.Gemma2Config(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
    )
    with pytest.raises(NotImplementedError, match="gemma2"):
        hf.config_from_hf(cfg)


def test_mixtral_non_silu_activation_rejected():
    cfg = transformers.MixtralConfig(
        hidden_act="gelu_pytorch_tanh", sliding_window=None
    )
    with pytest.raises(NotImplementedError, match="activation"):
        hf.moe_config_from_hf(cfg)


def test_mixtral_explicit_head_dim_maps():
    cfg = transformers.MixtralConfig(
        hidden_size=64, num_attention_heads=4, head_dim=32,
        sliding_window=None,
    )
    assert hf.moe_config_from_hf(cfg).head_dim == 32


def test_mixtral_rope_scaling_rejected():
    """The MoE attention stack has no rope-scaling slot: a Mixtral
    derivative carrying one (even 'llama3', which the DENSE bridge
    wires through) must hard-error, not load and diverge at every
    position (never-silently-diverge contract)."""
    cfg = transformers.MixtralConfig(
        sliding_window=None,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0,
            "low_freq_factor": 1.0, "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    )
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        hf.moe_config_from_hf(cfg)


def test_windowed_mixtral_matches_transformers():
    """A windowed Mixtral with the band genuinely active (24 > 8):
    logits parity with transformers, as the dense family's windowed
    Mistral has."""
    from infinistore_tpu.models import moe

    model = _tiny_mixtral(sliding_window=8)
    jcfg, params = hf.load_hf_moe(model, page_size=8, dtype="float32")
    assert jcfg.window == 8
    tokens = np.random.default_rng(63).integers(0, 128, (2, 24),
                                                dtype=np.int64)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens)).logits.numpy()
        full = _tiny_mixtral()(torch.from_numpy(tokens)).logits.numpy()
    ours, _, _ = moe.forward_dense(params, jcfg,
                                   jnp.asarray(tokens, jnp.int32))
    assert np.abs(np.asarray(ours) - ref).max() < 2e-4
    assert np.abs(full - ref).max() > 1e-2  # the band changed something


def test_mixtral_sliding_window_maps_onto_the_one_band():
    """The MoE bridge takes a window: MoEConfig.window, which the
    shared stack applies in every attention path whatever the
    feed-forward block (it refused any window before PR 35)."""
    jcfg = hf.moe_config_from_hf(transformers.MixtralConfig(
        num_hidden_layers=2, sliding_window=4096))
    assert jcfg.window == 4096 and not jcfg.two_kinds
    assert jcfg.layer_windows == (4096, 4096)
    assert hf.moe_config_from_hf(
        transformers.MixtralConfig(sliding_window=None)).window == 0


def test_mixtral_attention_bias_rejected():
    """self_attn.*.bias tensors have no slot in the MoE attention —
    dropping them silently would shift every attention output, so the
    bridge must refuse the checkpoint. (The bias probe runs before any
    weight is read, so a bare state dict keeps this test cheap — no
    model construction.)"""
    jcfg = hf.moe_config_from_hf(
        transformers.MixtralConfig(sliding_window=None)
    )
    sd = {"model.layers.0.self_attn.v_proj.bias": torch.zeros(8)}
    with pytest.raises(NotImplementedError, match="attention_bias"):
        hf.moe_params_from_hf(sd, jcfg)


# -- SmallThinker: full and banded layers, many small experts ---------------
SMALLTHINKER = dict(
    head_dim=16, hidden_size=64, max_position_embeddings=4096,
    model_name="tiny", moe_ffn_hidden_size=32,
    moe_num_active_primary_experts=2, moe_num_primary_experts=8,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_attention_heads=4, num_hidden_layers=4, num_key_value_heads=2,
    rms_norm_eps=1e-6, rope_layout=[0, 1, 1, 1], rope_scaling=None,
    rope_theta=1500000, sliding_window_layout=[0, 1, 1, 1],
    sliding_window_size=32, tie_word_embeddings=False, vocab_size=128)


def _st(**changed):
    import types

    return types.SimpleNamespace(**{**SMALLTHINKER, **changed})


def test_smallthinker_config_maps_every_key():
    cfg = hf.smallthinker_config_from_hf(_st(), page_size=8)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.n_experts, cfg.top_k,
            cfg.vocab_size, cfg.max_seq, cfg.rope_theta, cfg.norm_eps,
            cfg.act, cfg.early_router) == (
        64, 4, 4, 2, 16, 32, 8, 2, 128, 4096, 1.5e6, 1e-6, "relu", True)
    assert cfg.layer_windows == (0, 32, 32, 32)
    assert cfg.layer_ropes == (False, True, True, True)
    # a rope_layout that is not the window layout's is held too: the
    # per-layer spec carries the two apart
    odd = hf.smallthinker_config_from_hf(_st(rope_layout=[1, 0, 1, 0]))
    assert odd.layer_ropes == (True, False, True, False)
    assert odd.layer_windows == (0, 32, 32, 32)


@pytest.mark.parametrize("changed,match", [
    ({"moe_primary_router_apply_softmax": False}, "apply_softmax"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_layout": [0, 1, 1]}, "rope_layout"),
    ({"sliding_window_layout": [0, 1, 1, 1, 0]}, "sliding_window_layout"),
])
def test_smallthinker_bridge_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(NotImplementedError, match=match):
        hf.smallthinker_config_from_hf(_st(**changed))


def test_smallthinker_params_round_trip_a_state_dict():
    """transformers here has no SmallThinker class, so no parity with
    it: a state dict under the family's names, made from our own
    parameters ([out, in], as torch stores them), loads back equal; an
    attention bias or a missing expert is refused by name."""
    from infinistore_tpu.models import smallthinker

    cfg = hf.smallthinker_config_from_hf(_st(), page_size=8)
    params = smallthinker.init_params(jax.random.PRNGKey(3), cfg)
    sd = {"model.embed_tokens.weight": np.asarray(params["embed"]),
          "model.norm.weight": np.asarray(params["final_ln"]),
          "lm_head.weight": np.asarray(params["lm_head"]).T}
    for li, layer in enumerate(params["layers"]):
        p = f"model.layers.{li}."
        sd[p + "input_layernorm.weight"] = np.asarray(layer["ln1"])
        sd[p + "post_attention_layernorm.weight"] = np.asarray(layer["ln2"])
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj")):
            sd[p + f"self_attn.{theirs}.weight"] = np.asarray(layer[ours]).T
        m = p + "block_sparse_moe."
        sd[m + "primary_router.weight"] = np.asarray(layer["router"]).T
        for e in range(cfg.n_experts):
            for ours, theirs in (("e_gate", "gate"), ("e_up", "up"),
                                 ("e_down", "down")):
                sd[m + f"experts.{e}.{theirs}.weight"] = np.asarray(
                    layer[ours][e]).T
    back = hf.smallthinker_params_from_hf(sd, cfg)
    assert jax.tree_util.tree_structure(back) \
        == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    with pytest.raises(NotImplementedError, match="bias"):
        hf.smallthinker_params_from_hf(
            {**sd, "model.layers.0.self_attn.q_proj.bias": np.zeros(64)},
            cfg)
    del sd["model.layers.1.block_sparse_moe.experts.7.up.weight"]
    with pytest.raises(KeyError, match="experts.7.up"):
        hf.smallthinker_params_from_hf(sd, cfg)


# -- xing4_0: a latent cache, residual streams, a sigmoid router ------------
XING = dict(
    attention_bias=False, first_k_dense_replace=1, hidden_act="silu",
    hidden_size=64, intermediate_size=128, kv_lora_rank=32,
    max_position_embeddings=4096, model_type="xing4_0",
    moe_intermediate_size=32, moe_layer_freq=1, n_group=1,
    n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=2, num_hidden_layers=3,
    num_key_value_heads=4, num_nextn_predict_layers=0, hc_mult=4,
    hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=8, mscale=1,
                      mscale_all_dim=1,
                      original_max_position_embeddings=32, type="yarn"),
    routed_scaling_factor=2, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=16, vocab_size=128)


def _xing(**changed):
    import types

    return types.SimpleNamespace(**{**XING, **changed})


def test_xing_config_maps_every_key():
    cfg = hf.xing_config_from_hf(_xing(), page_size=8)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.vocab_size,
            cfg.max_seq, cfg.rope_theta, cfg.norm_eps, cfg.act) == (
        64, 3, 4, 128, 4096, 1e4, 1e-6, "silu")
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope, cfg.qk_rope,
            cfg.v_dim, cfg.head_dim) == (48, 32, 16, 8, 16, 24)
    assert (cfg.n_dense_lead, cfg.ffn_dense, cfg.d_ff, cfg.n_experts,
            cfg.top_k, cfg.n_shared, cfg.route_scale, cfg.router) == (
        1, 128, 32, 8, 2, 1, 2.0, "sigmoid")
    assert (cfg.hc_mult, cfg.hc_iters, cfg.hc_eps, cfg.hc_clamp) == (
        4, 20, 1e-6, 30.0)
    assert cfg.yarn == (8.0, 32, 32.0, 1.0, 1.0, 1.0)
    assert cfg.layer_kinds == ("latent",) * 3 and cfg.page_kinds == "c"
    assert cfg.kv_page_shape() == (8, 128)
    plain = hf.xing_config_from_hf(_xing(rope_scaling=None, hc_mult=1,
                                         n_shared_experts=0))
    assert plain.yarn == () and plain.hc_mult == 1 and plain.n_shared == 0


@pytest.mark.parametrize("changed,match", [
    ({"n_group": 8, "topk_group": 4}, "expert groups"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"num_nextn_predict_layers": 1}, "multi-token-prediction"),
    ({"mhc_h_res_clamp_min": -10}, "clamp"),
    ({"rope_scaling": {"rope_type": "llama3", "factor": 8.0}},
     "rope_scaling"),
    ({"first_k_dense_replace": 4}, "first_k_dense_replace"),
])
def test_xing_bridge_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(NotImplementedError, match=match):
        hf.xing_config_from_hf(_xing(**changed))


def test_yarn_frequencies_are_the_published_recipe():
    """decoder.rope under YaRN against the recipe written out in numpy
    (DeepSeek-V2's `yarn_find_correction_range` / `yarn_linear_ramp_mask`):
    fast dimensions keep their frequency, slow ones are divided by the
    factor, a linear ramp between."""
    import math

    import jax.numpy as jnp

    from infinistore_tpu.models import decoder

    dim, theta, factor, orig = 64, 10000.0, 64.0, 4096
    inv = theta ** (-np.arange(0, dim, 2) / dim)

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(32)), 0)
    high = min(math.ceil(correction_dim(1)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = inv / factor * ramp + inv * (1 - ramp)
    got, mult = decoder._yarn_scale_freqs(
        jnp.asarray(inv, jnp.float32), theta, (factor, orig, 32, 1, 1, 1))
    assert mult == 1.0
    assert np.allclose(np.asarray(got), want, rtol=1e-6)
    assert got[0] == pytest.approx(inv[0]) \
        and got[-1] == pytest.approx(inv[-1] / factor)
    assert decoder.yarn_mscale(64.0, 1.0) == pytest.approx(
        0.1 * math.log(64) + 1)
    # without YaRN the rotation is what it was
    x = jnp.ones((1, 4, 1, 8))
    pos = jnp.arange(4)[None]
    assert np.array_equal(np.asarray(decoder.rope(x, pos, theta)),
                          np.asarray(decoder.rope(x, pos, theta, yarn=())))


# -- cohere2_moe: a parallel block, window and full layers, a share ----------
COHERE = dict(
    attention_bias=False, expert_selection_fn="sigmoid",
    first_k_dense_replace=0, head_dim=16, hidden_act="silu", hidden_size=64,
    intermediate_size=32, layer_norm_eps=1e-5,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    logit_scale=0.5, max_position_embeddings=4096, model_type="cohere2_moe",
    norm_topk_prob=True, num_attention_heads=32, num_experts=8,
    num_experts_per_tok=2, num_hidden_layers=4, num_key_value_heads=2,
    num_shared_experts=4, position_embedding_type="rope_gptj",
    rms_norm_eps=None, rope_theta=50000, rotary_pct=1,
    shared_expert_combination_strategy="average", sliding_window=32,
    tie_word_embeddings=True, use_gated_activation=True,
    use_parallel_block=True, use_qk_norm=False, vocab_size=128)


def _cohere(**changed):
    import types

    return types.SimpleNamespace(**{**COHERE, **changed})


def test_cohere_config_maps_every_key():
    cfg = hf.cohere_moe_config_from_hf(_cohere(), page_size=8)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size, cfg.max_seq, cfg.rope_theta,
            cfg.norm_eps, cfg.act) == (
        64, 4, 32, 2, 16, 128, 4096, 5e4, 1e-5, "silu")
    assert cfg.layer_windows == (32, 32, 32, 0)
    assert cfg.layer_ropes == (True, True, True, False)
    assert (cfg.d_ff, cfg.n_experts, cfg.top_k, cfg.n_shared, cfg.router,
            cfg.shared_mean) == (32, 8, 2, 4, "sigmoid", True)
    # every expert the router scores is held, unless the file says which
    assert (cfg.n_routed, cfg.first_expert, cfg.holds_share) == (0, 0, False)
    assert cfg.norm_center and cfg.rope_adjacent and cfg.two_kinds
    assert cfg.logits_div == 2.0  # logits x logit_scale
    share = hf.cohere_moe_config_from_hf(_cohere(
        num_experts=2, expert_share={"router_width": 8, "first_expert": 4}))
    assert (share.n_routed, share.n_experts, share.first_expert,
            share.holds_share) == (8, 2, 4, True)
    # random weights: every matrix alike unless the file asks otherwise
    assert cfg.q_init_gain == share.q_init_gain == 1.0
    assert hf.cohere_moe_config_from_hf(_cohere(
        random_init={"query_gain": 4})).q_init_gain == 4.0
    one = hf.cohere_moe_config_from_hf(_cohere(
        layer_types=["full_attention"] * 4))
    assert one.layer_bands == () and one.window == 0 and not one.use_rope
    assert not one.two_kinds


@pytest.mark.parametrize("changed,match", [
    ({"use_qk_norm": True}, "use_qk_norm"),
    ({"attention_bias": True}, "attention_bias"),
    ({"first_k_dense_replace": 1}, "leading dense"),
    ({"expert_selection_fn": "softmax"}, "expert_selection_fn"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"shared_expert_combination_strategy": "sum"},
     "shared_expert_combination_strategy"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"use_gated_activation": False}, "use_gated_activation"),
    ({"rotary_pct": 0.5}, "rotary_pct"),
    ({"position_embedding_type": "rope_neox"}, "position_embedding_type"),
    ({"use_parallel_block": False}, "use_parallel_block"),
    ({"tie_word_embeddings": False}, "untied"),
    ({"layer_types": ["sliding_attention"] * 3}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 3 + ["linear_attention"]},
     "layer_types"),
])
def test_cohere_bridge_refuses_what_is_not_implemented(changed, match):
    with pytest.raises(NotImplementedError, match=match):
        hf.cohere_moe_config_from_hf(_cohere(**changed))


# ---------------------------------------------------------------------------
# glm_moe_dsa (GLM-5.2): latent attention under a learned selection
# ---------------------------------------------------------------------------

GLM = dict(
    attention_bias=False, ep_size=1, first_k_dense_replace=1, head_dim=16,
    hidden_act="silu", hidden_size=64, index_head_dim=16, index_n_heads=4,
    index_share_for_mtp_iteration=True, index_skip_topk_offset=3,
    index_topk=32, index_topk_freq=4, index_topk_pattern=None,
    indexer_rope_interleave=True,
    indexer_types=["full", "shared", "shared", "shared", "full"],
    intermediate_size=128, kv_lora_rank=32, max_position_embeddings=4096,
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    model_type="glm_moe_dsa", moe_intermediate_size=32, moe_layer_freq=1,
    n_group=1, n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=4, num_experts_per_tok=2, num_hidden_layers=5,
    num_key_value_heads=4, num_nextn_predict_layers=0, q_lora_rank=48,
    qk_head_dim=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    rms_norm_eps=1e-5, rope_interleave=True,
    rope_parameters={"rope_theta": 8000000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=1, topk_method="noaux_tc",
    v_head_dim=16, vocab_size=128,
)


def _glm(**changed):
    from types import SimpleNamespace

    return SimpleNamespace(**{**GLM, **changed})


def test_glm_config_maps_every_key():
    from infinistore_tpu.models.hf import glm_dsa_config_from_hf

    cfg = glm_dsa_config_from_hf(_glm(), page_size=8, dtype="bfloat16")
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab_size, cfg.max_seq, cfg.page_size, cfg.dtype) == (
        64, 5, 4, 4, 128, 4096, 8, "bfloat16")
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope, cfg.qk_rope,
            cfg.v_dim, cfg.head_dim) == (48, 32, 16, 8, 16, 24)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (4, 16, 32)
    assert cfg.indexer_kinds == ("full", "shared", "shared", "shared",
                                 "full") and cfg.index_layers == (0, 4)
    assert cfg.dense_layers == (True, False, False, False, False)
    assert (cfg.ffn_dense, cfg.d_ff, cfg.n_experts, cfg.top_k, cfg.n_shared,
            cfg.route_scale, cfg.router) == (128, 32, 8, 2, 1, 2.5,
                                             "sigmoid")
    assert (cfg.n_routed, cfg.first_expert) == (0, 0) and not cfg.holds_share
    assert cfg.rope_theta == 8e6 and cfg.norm_eps == 1e-5
    assert cfg.rope_adjacent and cfg.index_rope_adjacent and cfg.yarn == ()
    assert cfg.q_init_gain == 1.0
    # half-split rotary where the config says so
    split = glm_dsa_config_from_hf(_glm(rope_interleave=False,
                                        indexer_rope_interleave=False))
    assert not split.rope_adjacent and not split.index_rope_adjacent
    # one chip's share, and the random query projection's width
    share = glm_dsa_config_from_hf(_glm(
        n_routed_experts=2,
        expert_share={"router_width": 8, "first_expert": 4},
        random_init={"query_gain": 4.0, "attn_out_gain": 0.125}))
    assert (share.n_experts, share.n_routed, share.first_expert) == (2, 8, 4)
    assert share.holds_share and share.q_init_gain == 4.0
    assert (cfg.o_init_gain, cfg.down_init_gain) == (1.0, 1.0)
    assert (share.o_init_gain, share.down_init_gain) == (0.125, 1.0)
    assert glm_dsa_config_from_hf(_glm(
        random_init={"ffn_out_gain": 0.25})).down_init_gain == 0.25
    # without the list, first_k_dense_replace says which layers are dense
    lead = glm_dsa_config_from_hf(_glm(mlp_layer_types=None,
                                       first_k_dense_replace=2))
    assert lead.dense_layers == (True, True, False, False, False)
    # the page contract's two kinds
    assert cfg.page_kinds == "ci" and cfg.page_shape("i") == (8, 16)
    assert cfg.page_shape("c") == cfg.kv_page_shape() == (8, 128)


@pytest.mark.parametrize("changed,match", [
    (dict(n_group=2), "expert groups"),
    (dict(topk_group=2), "expert groups"),
    (dict(index_topk_pattern=[1, 0, 0, 0]), "index_topk_pattern"),
    (dict(indexer_types=["shared", "full", "shared", "shared", "full"]),
     "first layer is shared"),
    (dict(indexer_types=["full", "shared"]), "indexer_types list of 2"),
    (dict(indexer_types=["full", "none", "shared", "shared", "full"]),
     "indexer_types list"),
    (dict(mlp_layer_types=["dense", "sparse"]), "mlp_layer_types list of 2"),
    (dict(mlp_layer_types=["sparse", "dense", "sparse", "sparse",
                           "sparse"]), "at odds"),
    (dict(first_k_dense_replace=3), "at odds"),
    (dict(num_nextn_predict_layers=1), "multi-token-prediction"),
    (dict(rope_parameters={"rope_theta": 1e4, "rope_type": "yarn"}),
     "rope_type"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
])
def test_glm_bridge_refuses_what_is_not_implemented(changed, match):
    from infinistore_tpu.models.hf import glm_dsa_config_from_hf

    with pytest.raises(NotImplementedError, match=match):
        glm_dsa_config_from_hf(_glm(**changed))


# ---------------------------------------------------------------------------
# KeyeVL2 (Keye-VL-2.0-30B-A3B), the language model: grouped queries with
# q and k norms under a learned selection over K and V pages
# ---------------------------------------------------------------------------

# the catalog row's `config` (model-configs guide, architectures.jsonl),
# key for key
_KEYE_ROW = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=262144, max_window_layers=48, mlp_only_layers=[],
    model_type="KeyeVL2", moe_intermediate_size=768, norm_topk_prob=True,
    num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=48, num_key_value_heads=4, num_local_experts=128,
    rms_norm_eps=1e-06,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936,
)


def _keye(**changed):
    from types import SimpleNamespace

    return SimpleNamespace(**{**_KEYE_ROW, **changed})


def test_keye_config_maps_the_catalog_rows_keys():
    from infinistore_tpu.models.hf import keye_config_from_hf

    cfg = keye_config_from_hf(_keye(), page_size=16, dtype="bfloat16")
    assert type(cfg).__name__ == "KeyeConfig"
    assert (cfg.vocab_size, cfg.d_model, cfg.n_layers, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.page_size, cfg.dtype) == (
        151936, 2048, 48, 32, 4, 128, 16, "bfloat16")
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.router, cfg.n_shared,
            cfg.n_routed) == (128, 8, 768, "softmax", 0, 0)
    assert not cfg.holds_share
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk, cfg.index_rope,
            cfg.index_width) == (16, 64, 2048, 64, 128)
    assert cfg.indexer_kinds == ("full",) * 48
    assert cfg.rope_theta == 1e7 and cfg.norm_eps == 1e-6
    assert not cfg.rope_adjacent and not cfg.index_rope_adjacent
    assert cfg.rope_scaling == () and cfg.max_seq == 262144
    assert cfg.layer_kinds == ("attention",) * 48 and not cfg.two_kinds
    assert (cfg.q_init_gain, cfg.o_init_gain, cfg.down_init_gain) == (
        1.0, 1.0, 1.0)
    init = keye_config_from_hf(_keye(random_init={
        "query_gain": 1.5, "attn_out_gain": 0.03125, "ffn_out_gain": 0.25}))
    assert (init.q_init_gain, init.o_init_gain, init.down_init_gain) == (
        1.5, 0.03125, 0.25)
    # the page contract's three kinds
    assert cfg.page_kinds == "kvi"
    assert cfg.page_shape("k") == cfg.page_shape("v") \
        == cfg.kv_page_shape() == (16, 4, 128)
    assert cfg.page_shape("i") == (16, 128)
    assert cfg.page_layers("i") == cfg.page_layers("k") == tuple(range(48))
    # the groups may come as namespaces, as a loaded config has them
    from types import SimpleNamespace
    ns = keye_config_from_hf(_keye(
        sa_config=SimpleNamespace(**_KEYE_ROW["sa_config"]),
        rope_scaling=SimpleNamespace(**_KEYE_ROW["rope_scaling"])))
    assert ns == keye_config_from_hf(_keye())


@pytest.mark.parametrize("changed,match", [
    (dict(vision_config={"depth": 27}), "vision_config"),
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(num_local_experts=16), "num_local_experts"),
    (dict(sa_config=None), "without sa_config"),
    (dict(sa_config={**_KEYE_ROW["sa_config"], "indexer_num_kv_heads": 2}),
     "indexer_num_kv_heads"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "rope_type"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
])
def test_keye_bridge_refuses_what_is_not_implemented(changed, match):
    from infinistore_tpu.models.hf import keye_config_from_hf

    with pytest.raises(NotImplementedError, match=match):
        keye_config_from_hf(_keye(**changed))
