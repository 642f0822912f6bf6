"""Operations and bytes the Xing4.0 family NEEDS, from the configuration
FILE's published keys alone: lib/costs.py's questions (weight_bytes,
decode_bytes, decode_flops, prefill_flops, page_bytes_all_layers,
store_block_bytes, snapshot_bytes, same signatures) answered for latent
attention, `hc_mult` residual streams, `first_k_dense_replace` leading
dense layers and `n_routed_experts` SwiGLU experts of which a token
uses `num_experts_per_tok`, beside `n_shared_experts` shared ones. Plus
the counts this family's own readers divide device time into: the
latent rows a decode step must read (`latent_attn_bytes`), the
attention and expansion FLOPs of an admission (`latent_prefill_flops`),
the streams' bytes of an admission (`hc_prefill_bytes`), the experts'
FLOPs of a prefill (`moe_prefill_flops`) and bytes of a decode step
(`moe_step_bytes`).

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs; every need
is a lower bound on what the stage must move and knows nothing of the
implementation: a cached token needs kv_lora_rank + qk_rope_head_dim
values a layer (576 x 2 B = 1,152 B) and each is read ONCE a decode
step for all heads. The one place the program's layout shows is what
the STORE holds (`page_bytes_all_layers`, `store_block_bytes`: they
size the store's pool and its allocation unit): a row is stored as it
lies in the pool, rounded up to a tile of 128 lanes (640 of them;
models/xing.py `latent_width`; tests/benchmark/test_bench_xing.py
holds the two equal).
"""

LANES = 128


def _dims(conf):
    lead = conf["first_k_dense_replace"]
    return {
        "d": conf["hidden_size"], "H": conf["num_attention_heads"],
        "qr": conf["q_lora_rank"], "R": conf["kv_lora_rank"],
        "nope": conf["qk_nope_head_dim"], "rope": conf["qk_rope_head_dim"],
        "vd": conf["v_head_dim"], "ffd": conf["intermediate_size"],
        "ff": conf["moe_intermediate_size"], "E": conf["n_routed_experts"],
        "k": conf["num_experts_per_tok"], "ns": conf["n_shared_experts"],
        "L": conf["num_hidden_layers"], "lead": lead,
        "sparse": conf["num_hidden_layers"] - lead,
        "n": conf["hc_mult"], "V": conf["vocab_size"],
    }


def attn_params(conf):
    """Wqa, Wqb, Wkva, Wkvb, Wo and the two inner norms."""
    m = _dims(conf)
    return (m["d"] * m["qr"] + m["qr"] * m["H"] * (m["nope"] + m["rope"])
            + m["d"] * (m["R"] + m["rope"])
            + m["R"] * m["H"] * (m["nope"] + m["vd"])
            + m["H"] * m["vd"] * m["d"] + m["qr"] + m["R"])


def expert_params(conf):
    """One SwiGLU expert (gate, up, down), routed or shared."""
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def dense_mlp_params(conf):
    m = _dims(conf)
    return 3 * m["d"] * m["ffd"]


def router_params(conf):
    """The router and its selection bias."""
    m = _dims(conf)
    return m["d"] * m["E"] + m["E"]


def hc_f32_params(conf):
    """One sublayer's float32 mixing coefficients: the projection
    [n d, n n + 2 n], its bias and a_pre, a_post, a_res."""
    m = _dims(conf)
    c = m["n"] * m["n"] + 2 * m["n"]
    return m["n"] * m["d"] * c + c + 3


def hc_params(conf):
    """... and the norm over the n d stream values."""
    m = _dims(conf)
    return hc_f32_params(conf) + m["n"] * m["d"]


def layer_params(conf, sparse):
    m = _dims(conf)
    shared = attn_params(conf) + 2 * m["d"] + 2 * hc_params(conf)
    if not sparse:
        return shared + dense_mlp_params(conf)
    return shared + (m["E"] + m["ns"]) * expert_params(conf) \
        + router_params(conf)


def param_count(conf):
    """All parameters held: embedding, untied head, final norm,
    layers."""
    m = _dims(conf)
    return (2 * m["V"] * m["d"] + m["d"]
            + m["lead"] * layer_params(conf, False)
            + m["sparse"] * layer_params(conf, True))


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served (the router, its bias and the
    mixing coefficients are float32)."""
    m = _dims(conf)
    f32 = m["sparse"] * router_params(conf) \
        + 2 * m["L"] * hc_f32_params(conf)
    return param_count(conf) * itemsize + f32 * (4 - itemsize)


def latent_values(conf):
    """Values a cached token needs a layer: c and the shared key."""
    m = _dims(conf)
    return m["R"] + m["rope"]


def stored_row_values(conf):
    """... and as the store holds them: a row of whole lane tiles."""
    return -(-latent_values(conf) // LANES) * LANES


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store: what an
    offload WRITES, one row a token of every layer."""
    return _dims(conf)["L"] * stored_row_values(conf) * itemsize * page


def store_block_bytes(conf, page=16, itemsize=2):
    """The store's allocation unit for this cache: the largest power of
    two that divides the smallest object an offload writes, one layer's
    page (16 x 640 x 2 B = 20,480 B = 5 units of 4 KB). The store takes
    a power of two of KB and nothing else (config.verify; the first
    chip run of PR 40 ended there with a 20 KB unit)."""
    page_bytes = stored_row_values(conf) * itemsize * page
    return page_bytes & -page_bytes


def snapshot_bytes(conf, itemsize=2):
    return 0


def expected_experts_touched(conf, tokens):
    """Expected distinct routed experts `tokens` tokens touch when each
    picks k of E uniformly: E (1 - (1 - k/E) ** tokens)."""
    m = _dims(conf)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** max(0, tokens))


def latent_attn_bytes(conf, active, live_tokens, itemsize=2):
    """Cache rows the attention of one decode step must read: every
    live token of the active sequences, 576 values a layer, ONCE for
    all heads."""
    return _dims(conf)["L"] * live_tokens * latent_values(conf) * itemsize


def causal_pairs(suffix, prefix=0):
    return suffix * prefix + suffix * (suffix + 1) // 2


def latent_prefill_flops(conf, suffix, prefix=0):
    """FLOPs the attention of an admission of `suffix` tokens over
    `prefix` cached ones needs, unabsorbed: scores and weighted values
    over the pairs a query may see, and K and V of every head built
    from the rows of prefix and suffix (c Wkvb)."""
    m = _dims(conf)
    attend = m["H"] * causal_pairs(suffix, prefix) * 2 \
        * (m["nope"] + m["rope"] + m["vd"])
    expand = (prefix + suffix) * 2 * m["R"] * m["H"] * (m["nope"] + m["vd"])
    return m["L"] * (attend + expand)


def hc_prefill_bytes(conf, tokens, itemsize=2):
    """Bytes the residual path of an admission must move: around each
    of a layer's two sublayers the n streams are read once for the
    coefficients and the sublayer's input, and read and written once
    for the mix."""
    m = _dims(conf)
    return tokens * 2 * m["L"] * 3 * m["n"] * m["d"] * itemsize


def moe_step_bytes(conf, active, itemsize=2):
    """Bytes the expert blocks must read in one decode step: the routed
    experts `active` tokens touch in expectation, the shared ones and
    the routers. (A leading dense layer's MLP is not an expert
    block.)"""
    m = _dims(conf)
    return m["sparse"] * (
        (expected_experts_touched(conf, active) + m["ns"])
        * expert_params(conf) * itemsize + router_params(conf) * 4)


def moe_prefill_flops(conf, tokens):
    """FLOPs the expert blocks need for `tokens` prefilled tokens: k
    routed experts a token, the shared ones and the router."""
    m = _dims(conf)
    return 2 * tokens * m["sparse"] * (
        (m["k"] + m["ns"]) * expert_params(conf) + router_params(conf))


def _token_params(conf):
    """Parameters one token's matmuls touch outside attention's pairs:
    every layer's attention projections and mixing projections, the
    leading layers' MLP, the chosen and shared experts and routers."""
    m = _dims(conf)
    return (m["L"] * (attn_params(conf) + 2 * hc_f32_params(conf))
            + m["lead"] * dense_mlp_params(conf)
            + m["sparse"] * ((m["k"] + m["ns"]) * expert_params(conf)
                             + router_params(conf)))


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must read: attention weights, norms and
    mixing coefficients of every layer, the leading layers' MLP, the
    experts touched and the routers, the head, one embedding row a
    token, and the cache rows."""
    m = _dims(conf)
    weights = (m["L"] * ((attn_params(conf) + 2 * m["d"]
                          + 2 * m["n"] * m["d"]) * itemsize
                         + 2 * hc_f32_params(conf) * 4)
               + m["lead"] * dense_mlp_params(conf) * itemsize
               + moe_step_bytes(conf, active, itemsize)
               + (m["V"] * m["d"] + m["d"]) * itemsize
               + active * m["d"] * itemsize)
    return weights + latent_attn_bytes(conf, active, live_tokens, itemsize)


def decode_flops(conf, active, live_tokens):
    """... and its FLOPs: a token's matmuls (Wkvb's two halves as the
    absorbed query and output), the head, and scores and weighted
    values over the rows: 576 + 512 values a head a token a layer."""
    m = _dims(conf)
    return (2 * active * (_token_params(conf) + m["d"] * m["V"])
            + m["L"] * m["H"] * live_tokens * 2
            * (latent_values(conf) + m["R"]))


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs needed to prefill `suffix` tokens over `prefix` cached
    ones; the head for ONE position. Wkvb is counted with the expansion
    (it multiplies the rows of prefix and suffix alike)."""
    m = _dims(conf)
    wkvb = m["L"] * m["R"] * m["H"] * (m["nope"] + m["vd"])
    return (2 * suffix * (_token_params(conf) - wkvb)
            + latent_prefill_flops(conf, suffix, prefix)
            + 2 * m["d"] * m["V"])
