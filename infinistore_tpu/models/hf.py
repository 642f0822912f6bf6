"""HuggingFace ↔ infinistore_tpu weight bridge for the Llama family.

A user coming from the reference stack serves HF checkpoints; this
module loads a ``transformers`` Llama (model object or state dict) into
the JAX model in models/llama.py, so the same weights drive the paged-KV
engine, the store demos and the benchmarks. Covered checkpoint features:
GQA, tied embeddings, llama3-type ``rope_scaling`` (the Llama-3.1/3.2
long-context recipe) and per-projection attention biases — which makes
``Qwen2ForCausalLM``, ``MistralForCausalLM`` and ``GemmaForCausalLM``
checkpoints load directly (parity-tested — Gemma brings MQA, GeGLU,
zero-centered (1+w) RMSNorm, sqrt(d_model)-scaled embeddings and a
decoupled head_dim, which also unlocks Mistral-NeMo geometry), and
sliding-window attention maps onto ``LlamaConfig.window`` (banded masks in every attention path — a real
windowed Mistral matches transformers on prefill, paged decode, and
the engine's greedy stream); Qwen2's MIXED per-layer windowing
(``max_window_layers`` bottom layers full, the others banded) maps onto
the per-layer spec ``LlamaConfig.layer_bands``, which the serving
engine holds as two kinds of page. Unsupported features
(yarn/linear/dynamic rope, ``mlp_bias``) hard-error rather than
silently diverging. The conversion is pure
layout work: torch ``nn.Linear`` stores [out, in] and computes
``x @ W.T``, our params store [in, out] and compute ``x @ W`` — so every
projection transposes; head layouts, the half-split RoPE convention
(HF ``rotate_half``) and the SwiGLU wiring already agree, which the
logits-parity test (tests/test_hf_bridge.py) pins numerically against
``transformers`` itself.
"""

import numpy as np

from .llama import LlamaConfig


def config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a ``transformers.LlamaConfig`` onto :class:`LlamaConfig`.

    Raises on checkpoint features the JAX model does not implement —
    silently dropping them would load without error and diverge from
    the parity the bridge promises."""
    scaling = getattr(hf_cfg, "rope_scaling", None)
    rope_scaling = ()
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", ""))
        if rope_type == "llama3":
            # Llama-3.1/3.2 long-context checkpoints; applied in
            # decoder.rope via _llama3_scale_freqs, parity-pinned
            # against transformers in tests/test_hf_bridge.py.
            rope_scaling = (
                float(scaling["factor"]),
                float(scaling["low_freq_factor"]),
                float(scaling["high_freq_factor"]),
                float(scaling["original_max_position_embeddings"]),
            )
        elif rope_type != "default":
            raise NotImplementedError(
                f"rope_scaling type {rope_type!r} is not supported "
                "(implemented: 'llama3', 'default'); a linear/yarn/"
                "dynamic checkpoint would produce wrong logits at "
                "every position"
            )
    # Sliding-window attention maps onto LlamaConfig.window (one band
    # for every layer; decoder.py applies it in every attention path)
    # or, where the layers differ, onto the per-layer spec
    # LlamaConfig.layer_bands. The signalling differs per family:
    # Qwen2 carries sliding_window=4096 gated behind
    # use_sliding_window, with max_window_layers giving the count of
    # BOTTOM layers that keep full attention (between 0 and all:
    # mixed, the per-layer spec); Mistral's window is active whenever
    # sliding_window is not None, on every layer.
    window = 0
    layer_bands = ()
    if hasattr(hf_cfg, "use_sliding_window"):
        # transformers itself additionally gates SWA on sliding_window
        # being set: use_sliding_window=True with sliding_window=None
        # runs full attention there, so it must here too.
        if hf_cfg.use_sliding_window and hf_cfg.sliding_window is not None:
            mwl = int(getattr(hf_cfg, "max_window_layers", 0))
            if mwl >= hf_cfg.num_hidden_layers:
                window = 0  # every layer below the SWA cutoff: all full
            elif mwl == 0:
                window = int(hf_cfg.sliding_window)
            else:
                # mixed per-layer sliding window: the bottom mwl layers
                # full, the others banded
                layer_bands = (0,) * mwl + (int(hf_cfg.sliding_window),) \
                    * (hf_cfg.num_hidden_layers - mwl)
    else:
        sw = getattr(hf_cfg, "sliding_window", None)
        if sw is not None:
            window = int(sw)
    # Decoupled head_dim (Gemma, Mistral-NeMo): carried as an override
    # so q/k/v/o shapes and the attention scale follow the checkpoint.
    hd = getattr(hf_cfg, "head_dim", None)
    derived = hf_cfg.hidden_size // hf_cfg.num_attention_heads
    head_dim_override = hd if (hd is not None and hd != derived) else 0
    # Activation: Llama/Qwen2/Mistral are SwiGLU (silu); Gemma is GeGLU
    # (gelu_pytorch_tanh == jax.nn.gelu approximate).
    hidden_act = getattr(hf_cfg, "hidden_act",
                         getattr(hf_cfg, "hidden_activation", None)) \
        or "silu"
    if hidden_act in ("silu", "swish"):
        act = "silu"
    elif hidden_act in ("gelu_pytorch_tanh", "gelu_new", "gelu_fast"):
        act = "gelu"          # tanh approximation
    elif hidden_act == "gelu":
        act = "gelu_exact"    # erf form — a distinct function
    else:
        raise NotImplementedError(
            f"hidden_act {hidden_act!r} has no JAX mapping"
        )
    # Gemma conventions: zero-centered RMSNorm weights applied as
    # (1 + w), and embeddings scaled by sqrt(hidden_size). Gemma-2/3
    # add logit softcapping, pre/post-FFN norms and per-layer
    # windowing the JAX model has no slots for — loading them through
    # the gemma-1 mapping would silently diverge, so they hard-error.
    model_type = getattr(hf_cfg, "model_type", "")
    if model_type.startswith("gemma") and model_type != "gemma":
        raise NotImplementedError(
            f"{model_type} checkpoints carry logit softcapping and "
            "extra per-layer norms the JAX model does not implement "
            "(gemma-1 is supported)"
        )
    is_gemma = model_type == "gemma"
    return LlamaConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_key_value_heads,
        d_ff=hf_cfg.intermediate_size,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(hf_cfg.rope_theta),
        rope_scaling=rope_scaling,
        window=window,
        layer_bands=layer_bands,
        act=act,
        norm_plus_one=is_gemma,
        embed_scale=float(hf_cfg.hidden_size) ** 0.5 if is_gemma else 1.0,
        head_dim_override=head_dim_override,
        norm_eps=float(hf_cfg.rms_norm_eps),
        dtype=dtype,
    )


def _t(sd, name, dtype):
    import jax.numpy as jnp

    w = sd[name]
    if hasattr(w, "detach"):  # torch tensor
        w = w.detach().cpu().numpy()
    return jnp.asarray(np.asarray(w), dtype=dtype)


def params_from_hf(model_or_state_dict, cfg: LlamaConfig):
    """Build the models/llama.py parameter pytree from a HF Llama model
    (``LlamaForCausalLM``) or its state dict."""
    sd = model_or_state_dict
    if hasattr(sd, "state_dict"):
        sd = sd.state_dict()
    dt = cfg.jdtype
    layers = []
    for li in range(cfg.n_layers):
        p = f"model.layers.{li}."
        layer = {
            "ln1": _t(sd, p + "input_layernorm.weight", dt),
            "wq": _t(sd, p + "self_attn.q_proj.weight", dt).T,
            "wk": _t(sd, p + "self_attn.k_proj.weight", dt).T,
            "wv": _t(sd, p + "self_attn.v_proj.weight", dt).T,
            "wo": _t(sd, p + "self_attn.o_proj.weight", dt).T,
            "ln2": _t(sd, p + "post_attention_layernorm.weight", dt),
            "w_gate": _t(sd, p + "mlp.gate_proj.weight", dt).T,
            "w_up": _t(sd, p + "mlp.up_proj.weight", dt).T,
            "w_down": _t(sd, p + "mlp.down_proj.weight", dt).T,
        }
        # attention_bias=True checkpoints (HF Llama with biases; the
        # Qwen2 family geometry) carry per-projection biases — map
        # whichever are present (Qwen2 has q/k/v but no o bias).
        for ours, theirs in (("bq", "q_proj"), ("bk", "k_proj"),
                             ("bv", "v_proj"), ("bo", "o_proj")):
            name = p + f"self_attn.{theirs}.bias"
            if name in sd:
                layer[ours] = _t(sd, name, dt)
        # mlp_bias=True checkpoints carry gate/up/down biases the JAX
        # MLP has no slots for — hard-error rather than loading a model
        # that silently diverges (the bridge's contract).
        for theirs in ("gate_proj", "up_proj", "down_proj"):
            if p + f"mlp.{theirs}.bias" in sd:
                raise NotImplementedError(
                    "mlp_bias=True checkpoints are not supported: "
                    f"{p}mlp.{theirs}.bias has no parameter slot"
                )
        layers.append(layer)
    embed = _t(sd, "model.embed_tokens.weight", dt)
    if "lm_head.weight" in sd:
        lm_head = _t(sd, "lm_head.weight", dt).T
    else:  # tied embeddings
        lm_head = embed.T
    return {
        "embed": embed,
        "layers": layers,
        "final_ln": _t(sd, "model.norm.weight", dt),
        "lm_head": lm_head,
    }


def load_hf(model_or_state_dict, hf_cfg=None, page_size=16,
            dtype="float32"):
    """One-call bridge: returns (cfg, params). ``hf_cfg`` defaults to
    ``model.config`` when a model object is passed."""
    if hf_cfg is None:
        hf_cfg = model_or_state_dict.config
    cfg = config_from_hf(hf_cfg, page_size=page_size, dtype=dtype)
    return cfg, params_from_hf(model_or_state_dict, cfg)


__all__ = ["config_from_hf", "params_from_hf", "load_hf",
           "moe_config_from_hf", "moe_params_from_hf", "load_hf_moe",
           "hybrid_config_from_hf", "hybrid_params_from_hf",
           "load_hf_hybrid", "smallthinker_config_from_hf",
           "smallthinker_params_from_hf", "xing_config_from_hf",
           "cohere_moe_config_from_hf"]


def moe_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a ``transformers.MixtralConfig`` onto :class:`MoEConfig`.

    capacity_factor is set to n_experts / top_k so per-expert capacity
    equals the token count — NO token is ever dropped, which is the
    condition for exact routing parity with HF's dense top-k (GShard
    capacity is this implementation's scaling knob, not Mixtral's
    semantics; production serving can lower it and accept drops)."""
    from .moe import MoEConfig

    # One band on every layer, as Mistral's: MoEConfig.window, which
    # the shared stack (models/decoder.py) applies in every attention
    # path, whatever the feed-forward block.
    sw = getattr(hf_cfg, "sliding_window", None)
    # Never silently diverge (the dense bridge's contract): MoEConfig
    # has LlamaConfig's rope_scaling slot and the shared stack
    # (models/decoder.py) applies it, but no test pins a scaled Mixtral
    # against transformers, so ANY scaling — including 'llama3', which
    # the dense bridge wires through with such a test — is refused and
    # not passed on unverified.
    scaling = getattr(hf_cfg, "rope_scaling", None)
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type", ""))
        if rope_type != "default":
            raise NotImplementedError(
                f"rope_scaling type {rope_type!r} is not supported by "
                "the MoE bridge (no parity test against transformers "
                "for a scaled Mixtral)"
            )
    if getattr(hf_cfg, "hidden_act", "silu") not in ("silu", "swish"):
        raise NotImplementedError(
            f"MoE expert activation {hf_cfg.hidden_act!r}: the expert "
            "FFN hardcodes SwiGLU (silu)"
        )
    hd = getattr(hf_cfg, "head_dim", None)
    derived = hf_cfg.hidden_size // hf_cfg.num_attention_heads
    return MoEConfig(
        head_dim_override=(
            hd if (hd is not None and hd != derived) else 0
        ),
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_key_value_heads,
        d_ff=hf_cfg.intermediate_size,
        n_experts=hf_cfg.num_local_experts,
        top_k=hf_cfg.num_experts_per_tok,
        capacity_factor=float(hf_cfg.num_local_experts)
        / hf_cfg.num_experts_per_tok,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(hf_cfg.rope_theta),
        window=0 if sw is None else int(sw),
        norm_eps=float(hf_cfg.rms_norm_eps),
        dtype=dtype,
    )


def moe_params_from_hf(model_or_state_dict, cfg):
    """Build the models/moe.py parameter pytree from a HF Mixtral model
    (``MixtralForCausalLM``) or its state dict: per-expert w1/w3/w2
    ([out, in] each) stack onto the leading E axis as e_gate/e_up/e_down
    ([E, in, out]); the router gate transposes like every projection."""
    import jax.numpy as jnp

    sd = model_or_state_dict
    if hasattr(sd, "state_dict"):
        sd = sd.state_dict()
    dt = cfg.jdtype
    layers = []
    for li in range(cfg.n_layers):
        p = f"model.layers.{li}."
        m = p + "block_sparse_moe."
        # attention_bias=True checkpoints carry per-projection biases the
        # MoE attention has no parameter slots for — hard-error rather
        # than dropping them (the dense bridge maps these; here they
        # would silently vanish and shift every attention output).
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            if p + f"self_attn.{proj}.bias" in sd:
                raise NotImplementedError(
                    "attention_bias=True checkpoints are not supported "
                    f"by the MoE bridge: {p}self_attn.{proj}.bias has "
                    "no parameter slot"
                )
        layers.append({
            "ln1": _t(sd, p + "input_layernorm.weight", dt),
            "wq": _t(sd, p + "self_attn.q_proj.weight", dt).T,
            "wk": _t(sd, p + "self_attn.k_proj.weight", dt).T,
            "wv": _t(sd, p + "self_attn.v_proj.weight", dt).T,
            "wo": _t(sd, p + "self_attn.o_proj.weight", dt).T,
            "ln2": _t(sd, p + "post_attention_layernorm.weight", dt),
            "router": _t(sd, m + "gate.weight", "float32").T,
            "e_gate": jnp.stack([
                _t(sd, m + f"experts.{e}.w1.weight", dt).T
                for e in range(cfg.n_experts)
            ]),
            "e_up": jnp.stack([
                _t(sd, m + f"experts.{e}.w3.weight", dt).T
                for e in range(cfg.n_experts)
            ]),
            "e_down": jnp.stack([
                _t(sd, m + f"experts.{e}.w2.weight", dt).T
                for e in range(cfg.n_experts)
            ]),
        })
    embed = _t(sd, "model.embed_tokens.weight", dt)
    if "lm_head.weight" in sd:
        lm_head = _t(sd, "lm_head.weight", dt).T
    else:
        lm_head = embed.T
    return {
        "embed": embed,
        "layers": layers,
        "final_ln": _t(sd, "model.norm.weight", dt),
        "lm_head": lm_head,
    }


def load_hf_moe(model_or_state_dict, hf_cfg=None, page_size=16,
                dtype="float32"):
    """One-call Mixtral bridge: returns (cfg, params)."""
    if hf_cfg is None:
        hf_cfg = model_or_state_dict.config
    cfg = moe_config_from_hf(hf_cfg, page_size=page_size, dtype=dtype)
    return cfg, moe_params_from_hf(model_or_state_dict, cfg)


def hybrid_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a ``transformers.GraniteMoeHybridConfig`` (granite-4.0-h)
    onto :class:`models.hybrid.HybridConfig`: `layer_types` of Mamba-2
    and attention layers, the four multipliers, no positional
    embedding. Refuses what the JAX model does not implement: routed
    experts, rotary positions, biases, more than one group of B and
    C, an untied head."""
    from .hybrid import HybridConfig

    def refuse(what):
        raise NotImplementedError(
            f"granitemoehybrid: {what} is not implemented by "
            "models/hybrid.py")

    if getattr(hf_cfg, "num_local_experts", 0) > 0:
        refuse(f"num_local_experts={hf_cfg.num_local_experts} (routed "
               "experts beside the shared MLP)")
    pos = getattr(hf_cfg, "position_embedding_type", "nope")
    if pos != "nope":
        refuse(f"position_embedding_type={pos!r} (only 'nope')")
    if getattr(hf_cfg, "rope_scaling", None):
        refuse("rope_scaling")
    if getattr(hf_cfg, "attention_bias", False):
        refuse("attention_bias=True")
    if getattr(hf_cfg, "mamba_proj_bias", False):
        refuse("mamba_proj_bias=True")
    if not getattr(hf_cfg, "mamba_conv_bias", True):
        refuse("mamba_conv_bias=False")
    if getattr(hf_cfg, "mamba_n_groups", 1) != 1:
        refuse(f"mamba_n_groups={hf_cfg.mamba_n_groups} (one group)")
    if not getattr(hf_cfg, "tie_word_embeddings", True):
        refuse("tie_word_embeddings=False")
    if getattr(hf_cfg, "hidden_act", "silu") not in ("silu", "swish"):
        refuse(f"hidden_act={hf_cfg.hidden_act!r}")
    norm = getattr(hf_cfg, "normalization_function", "rmsnorm")
    if norm != "rmsnorm":
        refuse(f"normalization_function={norm!r}")
    kinds = tuple(hf_cfg.layer_types)
    if len(kinds) != hf_cfg.num_hidden_layers or set(kinds) - {
            "mamba", "attention"}:
        refuse(f"layer_types {sorted(set(kinds))} over "
               f"{hf_cfg.num_hidden_layers} layers")
    # Pack kv heads into cache rows of up to 128 lanes (head_dim 64:
    # two a row), as many as divide the kv heads.
    hd = hf_cfg.hidden_size // hf_cfg.num_attention_heads
    kv_pack = 1
    while hd * kv_pack * 2 <= 128 \
            and hf_cfg.num_key_value_heads % (kv_pack * 2) == 0:
        kv_pack *= 2
    heads, p = hf_cfg.mamba_n_heads, hf_cfg.mamba_d_head
    if heads * p != hf_cfg.mamba_expand * hf_cfg.hidden_size:
        refuse("mamba_n_heads * mamba_d_head != mamba_expand * "
               "hidden_size")
    return HybridConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_key_value_heads,
        d_ff=hf_cfg.shared_intermediate_size,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        norm_eps=float(hf_cfg.rms_norm_eps),
        embed_scale=float(hf_cfg.embedding_multiplier),
        attn_scale=float(hf_cfg.attention_multiplier),
        use_rope=False,
        kv_pack=kv_pack,
        residual_mult=float(hf_cfg.residual_multiplier),
        logits_div=float(hf_cfg.logits_scaling),
        layer_types=kinds,
        ssm_heads=heads,
        ssm_head_dim=p,
        ssm_state=hf_cfg.mamba_d_state,
        ssm_groups=1,
        ssm_conv=hf_cfg.mamba_d_conv,
        ssm_chunk=hf_cfg.mamba_chunk_size,
        dtype=dtype,
    )


def hybrid_params_from_hf(model_or_state_dict, cfg):
    """Build the models/hybrid.py parameter pytree from a HF
    ``GraniteMoeHybridForCausalLM`` or its state dict: `input_linear`
    ([2 * ff, d]) splits into gate and up, `conv1d.weight` [C, 1, K]
    becomes [K, C], every projection transposes."""
    sd = model_or_state_dict
    if hasattr(sd, "state_dict"):
        sd = sd.state_dict()
    dt = cfg.jdtype
    layers = []
    for li, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{li}."
        w_in = _t(sd, p + "shared_mlp.input_linear.weight", dt).T
        layer = {
            "ln1": _t(sd, p + "input_layernorm.weight", dt),
            "ln2": _t(sd, p + "post_attention_layernorm.weight", dt),
            "w_gate": w_in[:, :cfg.d_ff],
            "w_up": w_in[:, cfg.d_ff:],
            "w_down": _t(sd, p + "shared_mlp.output_linear.weight", dt).T,
        }
        if kind == "mamba":
            m = p + "mamba."
            layer.update({
                "in_proj": _t(sd, m + "in_proj.weight", dt).T,
                "conv_w": _t(sd, m + "conv1d.weight", dt)[:, 0, :].T,
                "conv_b": _t(sd, m + "conv1d.bias", dt),
                "A_log": _t(sd, m + "A_log", "float32"),
                "dt_bias": _t(sd, m + "dt_bias", "float32"),
                "D": _t(sd, m + "D", "float32"),
                "ssm_norm": _t(sd, m + "norm.weight", dt),
                "out_proj": _t(sd, m + "out_proj.weight", dt).T,
            })
        else:
            a = p + "self_attn."
            layer.update({
                "wq": _t(sd, a + "q_proj.weight", dt).T,
                "wk": _t(sd, a + "k_proj.weight", dt).T,
                "wv": _t(sd, a + "v_proj.weight", dt).T,
                "wo": _t(sd, a + "o_proj.weight", dt).T,
            })
        layers.append(layer)
    return {
        "embed": _t(sd, "model.embed_tokens.weight", dt),
        "layers": layers,
        "final_ln": _t(sd, "model.norm.weight", dt),
    }


def load_hf_hybrid(model_or_state_dict, hf_cfg=None, page_size=16,
                   dtype="float32"):
    """One-call granite-4.0-h bridge: returns (cfg, params)."""
    if hf_cfg is None:
        hf_cfg = model_or_state_dict.config
    cfg = hybrid_config_from_hf(hf_cfg, page_size=page_size, dtype=dtype)
    return cfg, hybrid_params_from_hf(model_or_state_dict, cfg)


def _layer_spec(bands, ropes):
    """LlamaConfig's fields for a per-layer spec of band and rotary:
    the two tuples, or where all layers are alike the one-band case
    (`window` / `use_rope`), which the engine holds in one pool."""
    one_band = len(set(bands)) == 1
    one_rope = len(set(ropes)) == 1
    return dict(window=bands[0] if one_band else 0,
                layer_bands=() if one_band else tuple(bands),
                use_rope=ropes[0] if one_rope else True,
                layer_rope=() if one_rope else tuple(ropes))


def smallthinker_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a SmallThinker ``config.json`` (PowerInfer/SmallThinker-
    21BA3B-Instruct; any object with its keys as attributes) onto
    :class:`models.smallthinker.SmallThinkerConfig`. Every key of the
    published config that shapes the model is read: the two per-layer
    lists become the per-layer spec (`layer_bands`, `layer_rope`; any
    combination of the two is held, a layer may be banded without
    rotary or full with it), the experts and the router's top-k the
    MoE fields. Refused, because models/smallthinker.py does not
    implement it: a router without softmax over the chosen logits
    (``moe_primary_router_apply_softmax: false``) or without
    renormalisation (``norm_topk_prob: false``), a ``rope_scaling``,
    tied embeddings, lists that are not one entry a layer. The
    activation (ReGLU) and the router's input (the attention block's
    normalised input) are the family's own: the config has no key for
    either."""
    from .smallthinker import SmallThinkerConfig

    def refuse(what):
        raise NotImplementedError(
            f"smallthinker: {what} is not implemented by "
            "models/smallthinker.py")

    if not getattr(hf_cfg, "moe_primary_router_apply_softmax", True):
        refuse("moe_primary_router_apply_softmax false (sigmoid gates)")
    if not getattr(hf_cfg, "norm_topk_prob", True):
        refuse("norm_topk_prob false (gates not renormalised on the "
               "chosen experts)")
    if getattr(hf_cfg, "rope_scaling", None):
        refuse(f"rope_scaling {hf_cfg.rope_scaling!r}")
    if getattr(hf_cfg, "tie_word_embeddings", False):
        refuse("tie_word_embeddings (the head is a leaf of its own)")
    n = hf_cfg.num_hidden_layers
    banded = list(hf_cfg.sliding_window_layout)
    rotates = list(hf_cfg.rope_layout)
    if len(banded) != n or len(rotates) != n:
        refuse(f"a sliding_window_layout of {len(banded)} or a "
               f"rope_layout of {len(rotates)} entries for {n} layers")
    band = int(hf_cfg.sliding_window_size)
    bands = tuple(band if b else 0 for b in banded)
    ropes = tuple(bool(r) for r in rotates)
    hd = getattr(hf_cfg, "head_dim", None)
    derived = hf_cfg.hidden_size // hf_cfg.num_attention_heads
    return SmallThinkerConfig(
        head_dim_override=hd if (hd is not None and hd != derived) else 0,
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=n,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_key_value_heads,
        d_ff=hf_cfg.moe_ffn_hidden_size,
        n_experts=hf_cfg.moe_num_primary_experts,
        top_k=hf_cfg.moe_num_active_primary_experts,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(hf_cfg.rope_theta),
        **_layer_spec(bands, ropes),
        norm_eps=float(hf_cfg.rms_norm_eps),
        dtype=dtype,
    )


def smallthinker_params_from_hf(model_or_state_dict, cfg):
    """Build the models/smallthinker.py parameter pytree (models/
    moe.py's leaves) from a SmallThinker state dict. Names as the
    family's published modeling file has them, as far as they could be
    written down without the network (transformers here has no
    SmallThinker class to check them against; a name that is not there
    raises the KeyError that says which): attention and norms as
    Llama's, ``block_sparse_moe.primary_router.weight`` and per expert
    ``block_sparse_moe.experts.{e}.{gate,up,down}.weight``, each
    [out, in] and transposed like every projection."""
    import jax.numpy as jnp

    sd = model_or_state_dict
    if hasattr(sd, "state_dict"):
        sd = sd.state_dict()
    dt = cfg.jdtype
    layers = []
    for li in range(cfg.n_layers):
        p = f"model.layers.{li}."
        m = p + "block_sparse_moe."
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            if p + f"self_attn.{proj}.bias" in sd:
                raise NotImplementedError(
                    f"smallthinker: {p}self_attn.{proj}.bias has no "
                    "parameter slot (no attention bias)")

        def experts(which):
            return jnp.stack([
                _t(sd, m + f"experts.{e}.{which}.weight", dt).T
                for e in range(cfg.n_experts)])

        layers.append({
            "ln1": _t(sd, p + "input_layernorm.weight", dt),
            "wq": _t(sd, p + "self_attn.q_proj.weight", dt).T,
            "wk": _t(sd, p + "self_attn.k_proj.weight", dt).T,
            "wv": _t(sd, p + "self_attn.v_proj.weight", dt).T,
            "wo": _t(sd, p + "self_attn.o_proj.weight", dt).T,
            "ln2": _t(sd, p + "post_attention_layernorm.weight", dt),
            "router": _t(sd, m + "primary_router.weight", "float32").T,
            "e_gate": experts("gate"),
            "e_up": experts("up"),
            "e_down": experts("down"),
        })
    return {
        "embed": _t(sd, "model.embed_tokens.weight", dt),
        "layers": layers,
        "final_ln": _t(sd, "model.norm.weight", dt),
        "lm_head": _t(sd, "lm_head.weight", dt).T,
    }


def xing_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a ``model_type: xing4_0`` ``config.json`` (XingChen-AGI/
    Xing4.0-29B-A4B; any object with its keys as attributes) onto
    :class:`models.xing.XingConfig`: latent attention's ranks and head
    widths, the leading dense layers and the routed and shared experts,
    the sigmoid router's scale, the residual streams and their Sinkhorn
    settings, YaRN. Refused, because models/xing.py does not implement
    it: expert groups (``n_group`` / ``topk_group`` over 1), another
    score than sigmoid or another selection than ``noaux_tc``, gates
    not normalised on the chosen, an activation other than silu,
    attention bias, experts on other than every layer after the dense
    ones, a rope scaling other than YaRN, a clamp that is not
    symmetric, tied embeddings, and a multi-token-prediction module
    (``num_nextn_predict_layers`` over 0: it is not held). Config
    only: a checkpoint's weights would also need the published rotary
    lane order and the mHC parameters' names, which no public key
    gives."""
    from .xing import XingConfig

    def refuse(what):
        raise NotImplementedError(
            f"xing4_0: {what} is not implemented by models/xing.py")

    g = lambda k, d=None: getattr(hf_cfg, k, d)  # noqa: E731
    if g("n_group", 1) != 1 or g("topk_group", 1) != 1:
        refuse(f"expert groups (n_group {g('n_group')}, topk_group "
               f"{g('topk_group')})")
    if g("scoring_func", "sigmoid") != "sigmoid":
        refuse(f"scoring_func {g('scoring_func')!r}")
    if g("topk_method", "noaux_tc") != "noaux_tc":
        refuse(f"topk_method {g('topk_method')!r}")
    if not g("norm_topk_prob", True):
        refuse("norm_topk_prob false")
    if g("hidden_act", "silu") != "silu":
        refuse(f"hidden_act {g('hidden_act')!r}")
    if g("attention_bias", False):
        refuse("attention_bias")
    if g("moe_layer_freq", 1) != 1:
        refuse(f"moe_layer_freq {g('moe_layer_freq')}")
    if g("tie_word_embeddings", False):
        refuse("tie_word_embeddings (the head is a leaf of its own)")
    if g("num_nextn_predict_layers", 0):
        refuse("a multi-token-prediction module "
               f"(num_nextn_predict_layers {g('num_nextn_predict_layers')})")
    lo, hi = g("mhc_h_res_clamp_min", -30), g("mhc_h_res_clamp_max", 30)
    if lo != -hi:
        refuse(f"a clamp that is not symmetric ({lo}, {hi})")
    yarn = ()
    rs = g("rope_scaling")
    if rs:
        rs = rs if isinstance(rs, dict) else vars(rs)
        if rs.get("type", rs.get("rope_type")) != "yarn":
            refuse(f"rope_scaling {rs!r}")
        yarn = (float(rs["factor"]),
                int(rs["original_max_position_embeddings"]),
                float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
                float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)))
    n = hf_cfg.num_hidden_layers
    lead = g("first_k_dense_replace", 0)
    if lead > n:
        refuse(f"first_k_dense_replace {lead} over {n} layers")
    return XingConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=n,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_attention_heads,
        head_dim_override=hf_cfg.qk_nope_head_dim + hf_cfg.qk_rope_head_dim,
        d_ff=hf_cfg.moe_intermediate_size,
        ffn_dense=hf_cfg.intermediate_size,
        n_dense_lead=lead,
        n_experts=hf_cfg.n_routed_experts,
        top_k=hf_cfg.num_experts_per_tok,
        n_shared=g("n_shared_experts", 0) or 0,
        route_scale=float(g("routed_scaling_factor", 1.0)),
        q_lora_rank=hf_cfg.q_lora_rank,
        kv_lora_rank=hf_cfg.kv_lora_rank,
        qk_nope=hf_cfg.qk_nope_head_dim,
        qk_rope=hf_cfg.qk_rope_head_dim,
        v_dim=hf_cfg.v_head_dim,
        hc_mult=g("hc_mult", 1),
        hc_iters=g("hc_sinkhorn_iters", 20),
        hc_eps=float(g("hc_eps", 1e-6)),
        hc_clamp=float(hi),
        yarn=yarn,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(hf_cfg.rope_theta),
        norm_eps=float(hf_cfg.rms_norm_eps),
        dtype=dtype,
    )


def cohere_moe_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a ``model_type: cohere2_moe`` ``config.json`` (CohereLabs/
    command-a-plus-05-2026; any object with its keys as attributes) onto
    :class:`models.cohere.CohereConfig`: ``layer_types`` becomes the
    per-layer spec (a sliding layer has the band ``sliding_window`` and
    rotates, a full layer has neither), the sigmoid router, the routed
    and the averaged shared experts, the mean-centred norm, rotary in
    adjacent pairs, ``logit_scale``. One chip's share of the routed
    experts is the group ``expert_share`` ({"router_width",
    "first_expert"}: the router scores ``router_width`` experts and
    this chip holds the ``num_experts`` with ids from ``first_expert``
    on); without it every expert the router scores is held. The group
    ``random_init`` ({"query_gain"}) is no published key either: how
    much wider than the other matrices a configuration without a
    checkpoint draws its query projection (``CohereConfig.q_init_gain``;
    absent: 1, every matrix alike). Refused,
    because models/cohere.py does not implement it: ``use_qk_norm``,
    ``attention_bias``, leading dense layers (``first_k_dense_replace``
    over 0), another selection function than sigmoid, gates not
    normalised on the chosen, another combination of the shared experts
    than their average, an activation other than gated silu, rotary
    over part of a head (``rotary_pct`` under 1) or in another form
    than ``rope_gptj``, a sequential block (``use_parallel_block``
    false), an untied head, a ``layer_types`` list that is not one
    known entry a layer. Config only: no public key names a
    checkpoint's tensors."""
    from .cohere import CohereConfig

    def refuse(what):
        raise NotImplementedError(
            f"cohere2_moe: {what} is not implemented by models/cohere.py")

    g = lambda k, d=None: getattr(hf_cfg, k, d)  # noqa: E731
    if g("use_qk_norm", False):
        refuse("use_qk_norm")
    if g("attention_bias", False):
        refuse("attention_bias")
    if g("first_k_dense_replace", 0):
        refuse("leading dense layers (first_k_dense_replace "
               f"{g('first_k_dense_replace')})")
    if g("expert_selection_fn", "sigmoid") != "sigmoid":
        refuse(f"expert_selection_fn {g('expert_selection_fn')!r}")
    if not g("norm_topk_prob", True):
        refuse("norm_topk_prob false")
    if g("num_shared_experts", 0) and g(
            "shared_expert_combination_strategy", "average") != "average":
        refuse("shared_expert_combination_strategy "
               f"{g('shared_expert_combination_strategy')!r}")
    if g("hidden_act", "silu") != "silu" \
            or not g("use_gated_activation", True):
        refuse(f"hidden_act {g('hidden_act')!r} with use_gated_activation "
               f"{g('use_gated_activation')!r}")
    if g("rotary_pct", 1) != 1:
        refuse(f"rotary_pct {g('rotary_pct')}")
    if g("position_embedding_type", "rope_gptj") != "rope_gptj":
        refuse(f"position_embedding_type {g('position_embedding_type')!r}")
    if not g("use_parallel_block", True):
        refuse("use_parallel_block false (a sequential block)")
    if not g("tie_word_embeddings", True):
        refuse("an untied head (the head is the embedding's rows)")
    n = hf_cfg.num_hidden_layers
    kinds = list(hf_cfg.layer_types)
    known = {"sliding_attention", "full_attention"}
    if len(kinds) != n or set(kinds) - known:
        refuse(f"a layer_types list of {len(kinds)} entries "
               f"{sorted(set(kinds))} for {n} layers")
    band = int(g("sliding_window", 0) or 0)
    bands = tuple(band if k == "sliding_attention" else 0 for k in kinds)
    ropes = tuple(k == "sliding_attention" for k in kinds)
    share = g("expert_share")
    if share is not None and not isinstance(share, dict):
        share = vars(share)
    init = g("random_init")
    if init is not None and not isinstance(init, dict):
        init = vars(init)
    hd = g("head_dim")
    derived = hf_cfg.hidden_size // hf_cfg.num_attention_heads
    return CohereConfig(
        head_dim_override=hd if (hd is not None and hd != derived) else 0,
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=n,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_key_value_heads,
        d_ff=hf_cfg.intermediate_size,
        n_experts=hf_cfg.num_experts,
        n_routed=int(share["router_width"]) if share else 0,
        first_expert=int(share["first_expert"]) if share else 0,
        top_k=hf_cfg.num_experts_per_tok,
        n_shared=g("num_shared_experts", 0) or 0,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(hf_cfg.rope_theta),
        **_layer_spec(bands, ropes),
        norm_eps=float(hf_cfg.layer_norm_eps),
        logits_div=1.0 / float(g("logit_scale", 1) or 1),
        q_init_gain=float(init["query_gain"]) if init else 1.0,
        dtype=dtype,
    )


def glm_dsa_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a ``model_type: glm_moe_dsa`` ``config.json`` (zai-org/
    GLM-5.2; any object with its keys as attributes) onto
    :class:`models.glm.GlmConfig`: latent attention's ranks and head
    widths, the indexer's heads, width and ``index_topk``,
    ``indexer_types`` as the per-layer spec of who owns an indexer and
    who borrows the nearest one below, ``mlp_layer_types`` as the spec
    of dense and sparse layers, the sigmoid router with its bias and
    scale, the shared expert, rotary in adjacent pairs
    (``rope_interleave``, ``indexer_rope_interleave``) at
    ``rope_parameters.rope_theta``. One chip's share of the routed
    experts is the group ``expert_share``, as
    :func:`cohere_moe_config_from_hf` reads it, and the widths a random
    checkpoint draws three kinds of matrix at the group ``random_init``
    (``query_gain``: Wqb; ``attn_out_gain``: Wo; ``ffn_out_gain``: the
    feed-forwards' down projections; each absent or 1: as every other
    matrix). The top-level
    ``head_dim`` equals ``qk_nope_head_dim`` and plays no part.
    Refused, because models/glm.py does not implement it: expert groups
    (``n_group`` / ``topk_group`` over 1), ``index_topk_pattern`` not
    null, an ``indexer_types`` list whose first layer is ``shared``,
    whose length is not the depth or with an unknown entry, a
    ``mlp_layer_types`` list likewise or at odds with
    ``first_k_dense_replace``, a multi-token-prediction module
    (``num_nextn_predict_layers`` over 0: it is not held), a
    ``rope_type`` other than default, another score than sigmoid or
    selection than ``noaux_tc``, gates not normalised on the chosen,
    an activation other than silu, attention bias, tied embeddings.
    Config only: no public key names a checkpoint's tensors."""
    from .glm import GlmConfig

    def refuse(what):
        raise NotImplementedError(
            f"glm_moe_dsa: {what} is not implemented by models/glm.py")

    def group(name):
        v = g(name)
        return v if v is None or isinstance(v, dict) else vars(v)

    g = lambda k, d=None: getattr(hf_cfg, k, d)  # noqa: E731
    if g("n_group", 1) != 1 or g("topk_group", 1) != 1:
        refuse(f"expert groups (n_group {g('n_group')}, topk_group "
               f"{g('topk_group')})")
    if g("index_topk_pattern") is not None:
        refuse(f"index_topk_pattern {g('index_topk_pattern')!r}")
    if g("num_nextn_predict_layers", 0):
        refuse("a multi-token-prediction module "
               f"(num_nextn_predict_layers {g('num_nextn_predict_layers')})")
    rp = group("rope_parameters") or {}
    if rp.get("rope_type", "default") != "default":
        refuse(f"rope_type {rp.get('rope_type')!r}")
    if g("scoring_func", "sigmoid") != "sigmoid":
        refuse(f"scoring_func {g('scoring_func')!r}")
    if g("topk_method", "noaux_tc") != "noaux_tc":
        refuse(f"topk_method {g('topk_method')!r}")
    if not g("norm_topk_prob", True):
        refuse("norm_topk_prob false")
    if g("hidden_act", "silu") != "silu":
        refuse(f"hidden_act {g('hidden_act')!r}")
    if g("attention_bias", False):
        refuse("attention_bias")
    if g("tie_word_embeddings", False):
        refuse("tie_word_embeddings (the head is a leaf of its own)")
    n = hf_cfg.num_hidden_layers
    owners = tuple(hf_cfg.indexer_types)
    if len(owners) != n or set(owners) - {"full", "shared"}:
        refuse(f"an indexer_types list of {len(owners)} entries "
               f"{sorted(set(owners))} for {n} layers")
    if owners[0] != "full":
        refuse("an indexer_types list whose first layer is shared (it "
               "has no layer below to borrow from)")
    lead = g("first_k_dense_replace", 0)
    mlps = tuple(g("mlp_layer_types") or
                 ["dense"] * lead + ["sparse"] * (n - lead))
    if len(mlps) != n or set(mlps) - {"dense", "sparse"}:
        refuse(f"a mlp_layer_types list of {len(mlps)} entries "
               f"{sorted(set(mlps))} for {n} layers")
    if mlps != ("dense",) * lead + ("sparse",) * (n - lead):
        refuse(f"a mlp_layer_types list at odds with "
               f"first_k_dense_replace {lead}")
    share, init = group("expert_share"), group("random_init")
    return GlmConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=n,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_attention_heads,
        head_dim_override=hf_cfg.qk_nope_head_dim + hf_cfg.qk_rope_head_dim,
        d_ff=hf_cfg.moe_intermediate_size,
        ffn_dense=hf_cfg.intermediate_size,
        dense_layers=tuple(m == "dense" for m in mlps),
        n_experts=hf_cfg.n_routed_experts,
        n_routed=int(share["router_width"]) if share else 0,
        first_expert=int(share["first_expert"]) if share else 0,
        top_k=hf_cfg.num_experts_per_tok,
        n_shared=g("n_shared_experts", 0) or 0,
        route_scale=float(g("routed_scaling_factor", 1.0)),
        q_lora_rank=hf_cfg.q_lora_rank,
        kv_lora_rank=hf_cfg.kv_lora_rank,
        qk_nope=hf_cfg.qk_nope_head_dim,
        qk_rope=hf_cfg.qk_rope_head_dim,
        v_dim=hf_cfg.v_head_dim,
        rope_adjacent=bool(g("rope_interleave", True)),
        index_heads=hf_cfg.index_n_heads,
        index_dim=hf_cfg.index_head_dim,
        index_topk=hf_cfg.index_topk,
        indexer_kinds=owners,
        index_rope_adjacent=bool(g("indexer_rope_interleave", True)),
        q_init_gain=float((init or {}).get("query_gain", 1.0)),
        o_init_gain=float((init or {}).get("attn_out_gain", 1.0)),
        down_init_gain=float((init or {}).get("ffn_out_gain", 1.0)),
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(rp.get("rope_theta", g("rope_theta", 10000.0))),
        norm_eps=float(hf_cfg.rms_norm_eps),
        dtype=dtype,
    )


def keye_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map the language model's keys of a ``model_type: KeyeVL2``
    ``config.json`` (Kwai-Keye/Keye-VL-2.0-30B-A3B; any object with its
    keys as attributes) onto :class:`models.keye.KeyeConfig`: grouped
    queries over ``num_key_value_heads`` heads of ``head_dim``, the
    group ``sa_config`` as the indexer's heads, width and ``topk`` on
    every layer, ``num_experts`` SwiGLU experts ``moe_intermediate_size``
    wide with ``num_experts_per_tok`` a token under a softmax router
    whose gates are renormalised over the chosen (``norm_topk_prob``),
    rotary in the halves layout at ``rope_theta``. Text alone: the
    three position rows of ``rope_scaling.mrope_section`` are equal for
    token ids, so the section goes unread; ``intermediate_size`` (no
    layer is dense), ``max_window_layers``, ``sliding_window`` and
    ``sa_config``'s ``q_chunk_size`` / ``kv_chunk_size`` (a kernel's
    tiles) go unread too. The widths a random checkpoint draws three
    kinds of matrix at are the group ``random_init``
    (:func:`glm_dsa_config_from_hf`'s keys; ``query_gain``: the q
    norm's weight).
    Refused, because models/keye.py does not implement it: a
    ``vision_config`` (a tower in front of the embedding: inputs are
    token ids), ``use_sliding_window`` true, ``mlp_only_layers`` not
    empty, ``decoder_sparse_step`` over 1, ``num_local_experts`` other
    than ``num_experts``, more than one index key a token
    (``indexer_num_kv_heads``), a ``rope_type`` other than default,
    gates not normalised on the chosen, an activation other than silu,
    attention bias, tied embeddings. Config only: no public key names a
    checkpoint's tensors."""
    from .keye import KeyeConfig

    def refuse(what):
        raise NotImplementedError(
            f"KeyeVL2: {what} is not implemented by models/keye.py")

    def group(name):
        v = g(name)
        return v if v is None or isinstance(v, dict) else vars(v)

    g = lambda k, d=None: getattr(hf_cfg, k, d)  # noqa: E731
    if g("vision_config") is not None:
        refuse("a vision_config (a tower in front of the embedding; the "
               "engine serves token ids)")
    if g("use_sliding_window", False):
        refuse("use_sliding_window (a selection over a window)")
    if g("mlp_only_layers"):
        refuse(f"mlp_only_layers {g('mlp_only_layers')!r}")
    if g("decoder_sparse_step", 1) != 1:
        refuse(f"decoder_sparse_step {g('decoder_sparse_step')}")
    if g("num_local_experts", hf_cfg.num_experts) != hf_cfg.num_experts:
        refuse(f"num_local_experts {g('num_local_experts')} of "
               f"{hf_cfg.num_experts} experts")
    sa = group("sa_config")
    if not sa:
        refuse("a model without sa_config (a dense-attention Qwen3-MoE)")
    if sa.get("indexer_num_kv_heads", 1) != 1:
        refuse(f"indexer_num_kv_heads {sa['indexer_num_kv_heads']}")
    rs = group("rope_scaling") or {}
    if rs.get("rope_type", rs.get("type", "default")) != "default":
        refuse(f"rope_type {rs.get('rope_type')!r}")
    if not g("norm_topk_prob", True):
        refuse("norm_topk_prob false")
    if g("hidden_act", "silu") != "silu":
        refuse(f"hidden_act {g('hidden_act')!r}")
    if g("attention_bias", False):
        refuse("attention_bias")
    if g("tie_word_embeddings", False):
        refuse("tie_word_embeddings (the head is a leaf of its own)")
    init = group("random_init") or {}
    return KeyeConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=hf_cfg.num_attention_heads,
        n_kv_heads=hf_cfg.num_key_value_heads,
        head_dim_override=g("head_dim", 0) or 0,
        d_ff=hf_cfg.moe_intermediate_size,
        n_experts=hf_cfg.num_experts,
        top_k=hf_cfg.num_experts_per_tok,
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        q_init_gain=float(init.get("query_gain", 1.0)),
        o_init_gain=float(init.get("attn_out_gain", 1.0)),
        down_init_gain=float(init.get("ffn_out_gain", 1.0)),
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(hf_cfg.rope_theta),
        norm_eps=float(hf_cfg.rms_norm_eps),
        dtype=dtype,
    )


def phi4flash_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map a Phi-4-mini-flash ``config.json`` (HF `phi4flash`,
    microsoft/Phi-4-mini-flash-reasoning; any object with its keys as
    attributes) onto :class:`models.phi_flash.PhiFlashConfig`. The
    layer layout is derived as the family's modeling code derives it
    from three keys: a layer l with ``l % mb_per_layer == 0`` is a
    Mamba-1 layer below ``num_hidden_layers // 2 + 2`` and a Gated
    Memory Unit from there on; the others are differential attention
    (a band of the scalar ``sliding_window`` below
    ``num_hidden_layers // 2``, full causal at ``num_hidden_layers //
    2 + 1``, whose K and V the cross layers above it attend) and
    cross-attention. The Mamba widths the catalog's config does not
    carry default to the family's (`mamba_d_state` 16, `mamba_d_conv`
    4, `mamba_expand` 2, `mamba_dt_rank` ceil(hidden / 16)).
    ``random_init`` ({"cross_out_gain"}) is no published key: how a
    benchmark configuration WITHOUT a checkpoint draws the cross
    layers' output projections (models/phi_flash.py init_params).
    Refused,
    because models/phi_flash.py does not implement it: an untied head,
    MLP or head biases, a head size that does not pack two to a row of
    128 lanes, an odd count of kv pairs, a window that is no multiple
    of the page, dropout. NO loader of published weights exists: one
    must permute the projections' columns from the published pairing
    by halves to this family's (models/phi_flash.py's docstring) and
    check it against the published modeling code."""
    from .phi_flash import PhiFlashConfig

    def refuse(what):
        raise NotImplementedError(
            f"phi4flash: {what} is not implemented by "
            "models/phi_flash.py")

    if not getattr(hf_cfg, "tie_word_embeddings", True):
        refuse("tie_word_embeddings false")
    if getattr(hf_cfg, "mlp_bias", False):
        refuse("mlp_bias")
    if getattr(hf_cfg, "lm_head_bias", False):
        refuse("lm_head_bias")
    if getattr(hf_cfg, "hidden_act", "silu") not in ("silu", "swish"):
        refuse(f"hidden_act={hf_cfg.hidden_act!r}")
    for drop in ("embd_pdrop", "resid_pdrop"):
        if getattr(hf_cfg, drop, 0):
            refuse(f"{drop} (inference only)")
    n, d = hf_cfg.num_hidden_layers, hf_cfg.hidden_size
    heads, kv = hf_cfg.num_attention_heads, hf_cfg.num_key_value_heads
    per = int(getattr(hf_cfg, "mb_per_layer", 2))
    band = int(hf_cfg.sliding_window)
    if per < 2 or n < 4 or n % 2:
        refuse(f"mb_per_layer={per} over {n} layers")
    if d % heads or (d // heads) * 2 > 128 or 128 % (d // heads):
        refuse(f"a head of {d}/{heads} lanes (two must fill a row of at "
               "most 128)")
    if kv % 2 or heads % 2 or (heads // 2) % (kv // 2):
        refuse(f"{heads} query and {kv} kv heads (pairs of both)")
    if band <= 0 or band % page_size:
        refuse(f"sliding_window={band} (a multiple of the page of "
               f"{page_size})")
    half = n // 2
    kinds, bands = [], []
    for l in range(n):
        mamba = l % per == 0
        if l >= half + 2:
            kinds.append("gmu" if mamba else "cross")
        else:
            kinds.append("mamba1" if mamba else "attention")
        bands.append(band if kinds[-1] == "attention" and l < half else 0)
    if kinds[half] != "mamba1" or kinds[half + 1] != "attention":
        refuse(f"a layout whose layer {half} is no Mamba layer or whose "
               f"layer {half + 1} no attention layer")
    return PhiFlashConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=d,
        n_layers=n,
        n_heads=heads,
        n_kv_heads=kv,
        d_ff=hf_cfg.intermediate_size,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        norm_eps=float(hf_cfg.layer_norm_eps),
        layer_types=tuple(kinds),
        layer_bands=tuple(bands),
        ssm_inner=int(getattr(hf_cfg, "mamba_expand", 2)) * d,
        ssm_state=int(getattr(hf_cfg, "mamba_d_state", 16)),
        ssm_conv=int(getattr(hf_cfg, "mamba_d_conv", 4)),
        dt_rank=int(getattr(hf_cfg, "mamba_dt_rank", 0)) or -(-d // 16),
        cross_out_gain=float((getattr(hf_cfg, "random_init", None)
                              or {}).get("cross_out_gain", 1.0)),
        dtype=dtype,
    )


def evabyte_config_from_hf(hf_cfg, page_size=16, dtype="float32"):
    """Map an EvaByte ``config.json`` (HF `evabyte`, EvaByte/EvaByte;
    any object with its keys as attributes) onto
    :class:`models.evabyte.EvaByteConfig`: ``window_size`` positions
    attended exactly, every earlier window as one row a chunk of
    ``chunk_size``, ``num_pred_heads`` heads of ``vocab_size`` logits
    (the program's ``vocab_size`` stays the byte vocabulary),
    ``norm_add_unit_offset`` the (1 + g) norm, ``fp32_skip_add`` the
    float32 stream, ``fp32_logits`` what `decoder.lm_head` returns
    anyway. ``random_init`` ({"phi_gain", "mu_gain"}) is no published
    key: how a benchmark configuration WITHOUT a checkpoint draws the
    summariser's two vectors (models/evabyte.py init_params).
    Refused, because models/evabyte.py does not implement it: another
    ``attention_class`` than "eva", a chunk that is not the page, a
    window that does not fold into whole pages of summary rows, fewer
    kv heads than heads, biases, rope scaling, a tied head, another
    activation than silu. NO loader of published weights exists: one
    must check the summariser's form (models/evabyte.py `fold`) and the
    head's layout against the published modeling code."""
    from .evabyte import EvaByteConfig

    def refuse(what):
        raise NotImplementedError(
            f"evabyte: {what} is not implemented by models/evabyte.py")

    g = lambda k, d=None: getattr(hf_cfg, k, d)  # noqa: E731
    if g("attention_class", "eva") != "eva":
        refuse(f"attention_class={g('attention_class')!r}")
    if g("attention_bias", False):
        refuse("attention_bias")
    if g("rope_scaling"):
        refuse("rope_scaling")
    if g("tie_word_embeddings", False):
        refuse("tie_word_embeddings")
    if g("hidden_act", "silu") != "silu":
        refuse(f"hidden_act={g('hidden_act')!r}")
    heads = hf_cfg.num_attention_heads
    if g("num_key_value_heads", heads) != heads:
        refuse(f"{g('num_key_value_heads')} kv heads under {heads} heads "
               "(the summariser's vectors are a head's)")
    chunk, window = int(hf_cfg.chunk_size), int(hf_cfg.window_size)
    if chunk != page_size:
        refuse(f"chunk_size={chunk} under pages of {page_size}")
    if window % (chunk * page_size):
        refuse(f"window_size={window} (a multiple of {chunk * page_size}: "
               "whole pages of summary rows)")
    init = g("random_init") or {}
    return EvaByteConfig(
        vocab_size=hf_cfg.vocab_size,
        d_model=hf_cfg.hidden_size,
        n_layers=hf_cfg.num_hidden_layers,
        n_heads=heads,
        n_kv_heads=heads,
        d_ff=hf_cfg.intermediate_size,
        max_seq=hf_cfg.max_position_embeddings,
        page_size=page_size,
        rope_theta=float(hf_cfg.rope_theta),
        norm_eps=float(hf_cfg.rms_norm_eps),
        norm_plus_one=bool(g("norm_add_unit_offset", True)),
        fp32_stream=bool(g("fp32_skip_add", True)),
        fold_window=window,
        fold_chunk=chunk,
        n_pred_heads=int(g("num_pred_heads", 1)),
        phi_gain=float(init.get("phi_gain", 1.0)),
        mu_gain=float(init.get("mu_gain", 1.0)),
        dtype=dtype,
    )
