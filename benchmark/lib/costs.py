"""Operations and bytes the algorithm NEEDS, from shapes alone. Kept
with the benchmark so that no PR that claims a gain can change them.
All functions take the configuration FILE's published keys.

This module knows one layer shape (GQA attention + SwiGLU x experts, a
K and a V page per layer). A configuration of another shape names a
module of its own under "program": {"costs": ...} (lib/serve.py:
costs_module) that answers the same questions for its layers and its
cache: weight_bytes, decode_bytes, decode_flops, prefill_flops,
page_bytes_all_layers, store_block_bytes and snapshot_bytes, each with
the signature it has here. No other file of the harness reads a layer
count or a KV geometry.

Conventions: a multiply-add is 2 FLOPs. Prefill needs the matmuls of
every prompt token it computes (suffix tokens), causal attention over
prefix + suffix, and the head for ONE position (the engine needs only
the last row; computing logits for every position is waste, not need).
A sparse-expert layer needs `num_experts_per_tok` experts per token:
dense dispatch over all experts is waste and shows as a low share.
"""


def _dims(conf):
    d = conf["hidden_size"]
    n_h, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // n_h
    return d, n_h, n_kv, hd, conf["intermediate_size"], \
        conf["num_hidden_layers"], conf["vocab_size"]


def attn_params_per_layer(conf):
    d, n_h, n_kv, hd, _, _, _ = _dims(conf)
    return d * n_h * hd + 2 * d * n_kv * hd + n_h * hd * d


def expert_params(conf):
    """One SwiGLU FFN (gate, up, down)."""
    d, _, _, _, ff, _, _ = _dims(conf)
    return 3 * d * ff


def n_experts(conf):
    return conf.get("num_local_experts", 1)


def experts_per_token(conf):
    return conf.get("num_experts_per_tok", 1)


def router_params_per_layer(conf):
    return conf["hidden_size"] * n_experts(conf) \
        if n_experts(conf) > 1 else 0


def param_count(conf):
    """All parameters held: embeddings, untied head, norms, layers."""
    d, _, _, _, _, L, V = _dims(conf)
    per_layer = (attn_params_per_layer(conf)
                 + n_experts(conf) * expert_params(conf)
                 + router_params_per_layer(conf) + 2 * d)
    head = 0 if conf.get("tie_word_embeddings") else V * d
    return V * d + head + d + L * per_layer


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served (bf16; the router of a sparse
    model is float32 in the program, 4 bytes)."""
    extra = conf["num_hidden_layers"] * router_params_per_layer(conf) * (
        4 - itemsize)
    return param_count(conf) * itemsize + extra


def kv_bytes_per_token(conf, itemsize=2):
    _, _, n_kv, hd, _, L, _ = _dims(conf)
    return 2 * L * n_kv * hd * itemsize


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store."""
    return kv_bytes_per_token(conf, itemsize) * page


def store_block_bytes(conf, page=16, itemsize=2):
    """The store's allocation unit: the smallest object an offload
    writes, here one K or one V page of one layer."""
    return page_bytes_all_layers(conf, page, itemsize) \
        // (2 * conf["num_hidden_layers"])


def snapshot_bytes(conf, itemsize=2):
    """Bytes an offload writes that do not grow with its pages (a
    recurrent state's snapshot): none where all the cache is K and V."""
    return 0


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs needed to prefill `suffix` tokens over `prefix` cached
    ones."""
    d, n_h, _, hd, _, L, V = _dims(conf)
    active = (attn_params_per_layer(conf)
              + experts_per_token(conf) * expert_params(conf)
              + router_params_per_layer(conf))
    matmul = 2 * suffix * L * active
    # causal: query i of the suffix sees prefix + i + 1 keys; QK^T and
    # PV are 2 * hd FLOPs each per (query, key, head).
    pairs = suffix * prefix + suffix * (suffix + 1) // 2
    attention = L * n_h * pairs * 4 * hd
    head = 2 * d * V
    return matmul + attention + head


def expected_experts_touched(conf, tokens):
    """Expected distinct experts a batch of `tokens` touches when each
    picks k of E uniformly: E * (1 - (1 - k/E) ** tokens)."""
    e, k = n_experts(conf), experts_per_token(conf)
    if e <= 1:
        return 1.0
    return e * (1.0 - (1.0 - k / e) ** max(0, tokens))


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must read: the layers' weights (for a
    sparse model the experts its `active` tokens touch, in expectation),
    the head, one embedding row per token, and the live KV pages of the
    active sequences (`live_tokens` tokens, rounded up to pages per
    sequence by the caller or not: the difference is under a page a
    sequence)."""
    d, _, _, _, _, L, V = _dims(conf)
    per_layer = (attn_params_per_layer(conf) + 2 * d) * itemsize \
        + expected_experts_touched(conf, active) * expert_params(conf) \
        * itemsize + router_params_per_layer(conf) * 4
    weights = L * per_layer + (V * d + d) * itemsize + active * d * itemsize
    return weights + live_tokens * kv_bytes_per_token(conf, itemsize)


def decode_flops(conf, active, live_tokens):
    d, n_h, _, hd, _, L, V = _dims(conf)
    act = (attn_params_per_layer(conf)
           + experts_per_token(conf) * expert_params(conf)
           + router_params_per_layer(conf))
    return 2 * active * (L * act + d * V) + L * n_h * live_tokens * 4 * hd
