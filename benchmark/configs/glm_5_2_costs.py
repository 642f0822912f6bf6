"""Operations and bytes the GLM-5.2 family NEEDS as ONE CHIP'S SHARE of
it, from the configuration FILE's keys alone: lib/costs.py's questions
(weight_bytes, decode_bytes, decode_flops, prefill_flops,
page_bytes_all_layers, store_block_bytes, snapshot_bytes, same
signatures) answered for latent attention under a learned selection
(`index_topk` rows a query), an indexer on the `indexer_types` "full"
layers, dense and sparse feed-forward layers by `mlp_layer_types`,
`n_routed_experts` routed experts HELD HERE of the
`expert_share.router_width` the router scores, and `n_shared_experts`
shared ones. Plus the counts this family's own readers divide device
time into: the selected rows a decode step must read
(`sparse_attn_bytes`), the index keys and indexer weights it must read
(`index_score_bytes`), the index scores' FLOPs of an admission
(`index_prefill_flops`), its attention's (`latent_prefill_flops`), the
experts' FLOPs of a prefill (`moe_prefill_flops`) and bytes of a decode
step (`moe_step_bytes`).

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs; every need
is a lower bound on what the stage must move and knows nothing of the
implementation. What the SELECTION leaves is what is counted: a query
attends min(keys it may see, index_topk) rows, a selected row needs
kv_lora_rank + qk_rope_head_dim values a layer (576 x 2 B = 1,152 B)
read ONCE for all heads, a live token's index key index_head_dim values
(256 B) in each layer that owns an indexer, and a causal pair of such a
layer 2 x index_n_heads x index_head_dim FLOPs (8,192). An admission's
attention is counted in the cheaper of its two forms (per-head K and V
built from every row of prefix and suffix, or the query absorbed as a
decode step's), so that neither program can read over 100 %. The one
place the program's layout shows is what the STORE holds
(`page_bytes_all_layers`, `store_block_bytes`): a latent row is stored
as it lies in the pool, 640 lanes; an index key as it is, 128.

The harness hands `decode_bytes` the SUM of the active sequences' live
tokens; a sequence reads min(its length, index_topk) rows, which the
sum does not determine: `selected_rows` is the LEAST the sum allows
(min(sum, active x index_topk)), exact where every active sequence is
past index_topk, as in this configuration's cell.
"""

LANES = 128


def _dims(conf):
    share = conf.get("expert_share") or {}
    mlps, owners = conf["mlp_layer_types"], conf["indexer_types"]
    return {
        "d": conf["hidden_size"], "H": conf["num_attention_heads"],
        "qr": conf["q_lora_rank"], "R": conf["kv_lora_rank"],
        "nope": conf["qk_nope_head_dim"], "rope": conf["qk_rope_head_dim"],
        "vd": conf["v_head_dim"], "ffd": conf["intermediate_size"],
        "ff": conf["moe_intermediate_size"], "E": conf["n_routed_experts"],
        "W": share.get("router_width", conf["n_routed_experts"]),
        "k": conf["num_experts_per_tok"], "ns": conf["n_shared_experts"],
        "L": conf["num_hidden_layers"], "V": conf["vocab_size"],
        "dense": sum(m == "dense" for m in mlps),
        "sparse": sum(m == "sparse" for m in mlps),
        "full": sum(o == "full" for o in owners),
        "Hi": conf["index_n_heads"], "Di": conf["index_head_dim"],
        "topk": conf["index_topk"],
    }


def attn_params(conf):
    """Wqa, Wqb, Wkva, Wkvb, Wo and the two inner norms."""
    m = _dims(conf)
    return (m["d"] * m["qr"] + m["qr"] * m["H"] * (m["nope"] + m["rope"])
            + m["d"] * (m["R"] + m["rope"])
            + m["R"] * m["H"] * (m["nope"] + m["vd"])
            + m["H"] * m["vd"] * m["d"] + m["qr"] + m["R"])


def indexer_params(conf):
    """WqI, WkI, Ww and the key LayerNorm's weight and bias."""
    m = _dims(conf)
    return (m["qr"] * m["Hi"] * m["Di"] + m["d"] * m["Di"]
            + m["d"] * m["Hi"] + 2 * m["Di"])


def expert_params(conf):
    """One SwiGLU expert (gate, up, down), routed or shared."""
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def dense_mlp_params(conf):
    m = _dims(conf)
    return 3 * m["d"] * m["ffd"]


def router_params(conf):
    """The router over EVERY published expert, and its bias."""
    m = _dims(conf)
    return m["d"] * m["W"] + m["W"]


def layer_params(conf, sparse, owner):
    m = _dims(conf)
    p = attn_params(conf) + 2 * m["d"] + owner * indexer_params(conf)
    if not sparse:
        return p + dense_mlp_params(conf)
    return p + (m["E"] + m["ns"]) * expert_params(conf) + router_params(conf)


def param_count(conf):
    """All parameters held: embedding, untied head, final norm,
    layers."""
    m = _dims(conf)
    return 2 * m["V"] * m["d"] + m["d"] + sum(
        layer_params(conf, mlp == "sparse", own == "full")
        for mlp, own in zip(conf["mlp_layer_types"], conf["indexer_types"]))


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served (the router and its bias are
    float32)."""
    m = _dims(conf)
    return param_count(conf) * itemsize \
        + m["sparse"] * router_params(conf) * (4 - itemsize)


def latent_values(conf):
    """Values a cached token needs a layer: c and the shared key."""
    m = _dims(conf)
    return m["R"] + m["rope"]


def stored_row_values(conf):
    """... and as the store holds them: a row of whole lane tiles."""
    return -(-latent_values(conf) // LANES) * LANES


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store: a latent
    page of every layer and an index page of every layer that owns an
    indexer (5 x 20,480 + 2 x 4,096 = 110,592 B)."""
    m = _dims(conf)
    return (m["L"] * stored_row_values(conf)
            + m["full"] * m["Di"]) * itemsize * page


def store_block_bytes(conf, page=16, itemsize=2):
    """The store's allocation unit: the largest power of two that
    divides every object an offload writes, a latent page (20,480 B)
    and an index page (4,096 B): 4 KB."""
    m = _dims(conf)
    latent = stored_row_values(conf) * itemsize * page
    index = m["Di"] * itemsize * page
    return min(latent & -latent, index & -index)


def snapshot_bytes(conf, itemsize=2):
    return 0


def expected_experts_touched(conf, tokens):
    """Expected distinct HELD experts `tokens` tokens touch when each
    picks k of the router's W evenly: E (1 - (1 - k/W) ** tokens)."""
    m = _dims(conf)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["W"]) ** max(0, tokens))


def selected_rows(conf, active, live_tokens):
    """Rows the attention of one layer reads in a decode step: each
    active sequence's min(length, index_topk), at the least the sum
    `live_tokens` allows (module docstring)."""
    return min(live_tokens, active * _dims(conf)["topk"])


def sparse_attn_bytes(conf, active, live_tokens, itemsize=2):
    """Cache rows the attention of one decode step must read: the
    selected rows of the active sequences, 576 values a layer, ONCE
    for all heads."""
    return _dims(conf)["L"] * selected_rows(conf, active, live_tokens) \
        * latent_values(conf) * itemsize


def index_score_bytes(conf, active, live_tokens, itemsize=2):
    """What the indexers of one decode step must read: every live
    token's index key in each layer that owns one, and those layers'
    indexer weights."""
    m = _dims(conf)
    return m["full"] * (live_tokens * m["Di"] + indexer_params(conf)) \
        * itemsize


def causal_pairs(suffix, prefix=0):
    return suffix * prefix + suffix * (suffix + 1) // 2


def selects(conf, suffix, prefix=0):
    """Whether an admission's queries see more keys than index_topk
    (else every row is selected and no score is needed)."""
    return prefix + suffix > _dims(conf)["topk"]


def selected_pairs(conf, suffix, prefix=0):
    """(query, attended row) pairs of an admission under the selection:
    query i of the suffix attends min(prefix + i + 1, index_topk)."""
    k = _dims(conf)["topk"]
    below = max(0, min(suffix, k - prefix))  # queries that see <= k keys
    return causal_pairs(below, prefix) + (suffix - below) * k


def index_prefill_flops(conf, suffix, prefix=0):
    """FLOPs the index scores of an admission need: every causal pair
    of each layer that owns an indexer, 2 x Hi x Di each; none where
    every row is selected."""
    if not selects(conf, suffix, prefix):
        return 0
    m = _dims(conf)
    return m["full"] * causal_pairs(suffix, prefix) * 2 * m["Hi"] * m["Di"]


def latent_prefill_flops(conf, suffix, prefix=0):
    """FLOPs the attention of an admission needs over the pairs the
    selection leaves, in the cheaper of its two forms: unabsorbed
    (scores and weighted values at nope + rope + vd values a head a
    pair, and K and V of every head built from the rows of prefix and
    suffix), or absorbed (R + rope + R values a head a pair, and the
    query and the output of every suffix token through Wkvb)."""
    m = _dims(conf)
    pairs = selected_pairs(conf, suffix, prefix)
    wkvb = 2 * m["R"] * m["H"] * (m["nope"] + m["vd"])
    expanded = m["H"] * pairs * 2 * (m["nope"] + m["rope"] + m["vd"]) \
        + (prefix + suffix) * wkvb
    absorbed = m["H"] * pairs * 2 * (latent_values(conf) + m["R"]) \
        + suffix * wkvb
    return m["L"] * min(expanded, absorbed)


def moe_step_bytes(conf, active, itemsize=2):
    """Bytes the expert blocks must read in one decode step: the held
    experts `active` tokens touch in expectation, the shared ones and
    the routers. (A dense layer's MLP is not an expert block.)"""
    m = _dims(conf)
    return m["sparse"] * (
        (expected_experts_touched(conf, active) + m["ns"])
        * expert_params(conf) * itemsize + router_params(conf) * 4)


def held_pairs_per_token(conf):
    """Chosen pairs a token brings to THIS chip in expectation."""
    m = _dims(conf)
    return m["k"] * m["E"] / m["W"]


def moe_prefill_flops(conf, tokens):
    """FLOPs the expert blocks need for `tokens` prefilled tokens: the
    chosen experts HELD HERE (k x held / width a token), the shared
    ones and the router."""
    m = _dims(conf)
    return 2 * tokens * m["sparse"] * (
        (held_pairs_per_token(conf) + m["ns"]) * expert_params(conf)
        + router_params(conf))


def _token_params(conf):
    """Parameters one token's matmuls touch outside attention's pairs
    and the index scores: every layer's attention projections, the
    owners' indexer projections, the dense layers' MLP, the chosen
    experts held here, the shared ones and the routers."""
    m = _dims(conf)
    return (m["L"] * attn_params(conf) + m["full"] * indexer_params(conf)
            + m["dense"] * dense_mlp_params(conf)
            + m["sparse"] * ((held_pairs_per_token(conf) + m["ns"])
                             * expert_params(conf) + router_params(conf)))


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must read: attention weights and norms of
    every layer, the dense layers' MLP, the experts touched and the
    routers, the head, one embedding row a token, and of the cache
    what the selection leaves: the selected rows and the index keys
    (with the indexers' weights)."""
    m = _dims(conf)
    weights = (m["L"] * (attn_params(conf) + 2 * m["d"]) * itemsize
               + m["dense"] * dense_mlp_params(conf) * itemsize
               + moe_step_bytes(conf, active, itemsize)
               + (m["V"] * m["d"] + m["d"]) * itemsize
               + active * m["d"] * itemsize)
    return weights + sparse_attn_bytes(conf, active, live_tokens, itemsize) \
        + index_score_bytes(conf, active, live_tokens, itemsize)


def decode_flops(conf, active, live_tokens):
    """... and its FLOPs: a token's matmuls, the head, the index
    scores over the live keys, and scores and weighted values over the
    selected rows (576 + 512 values a head a row a layer)."""
    m = _dims(conf)
    return (2 * active * (_token_params(conf) + m["d"] * m["V"])
            + m["full"] * live_tokens * 2 * m["Hi"] * m["Di"]
            + m["L"] * m["H"] * selected_rows(conf, active, live_tokens)
            * 2 * (latent_values(conf) + m["R"]))


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs needed to prefill `suffix` tokens over `prefix` cached
    ones; the head for ONE position. Wkvb is counted with the
    attention (`latent_prefill_flops`)."""
    m = _dims(conf)
    wkvb = m["L"] * m["R"] * m["H"] * (m["nope"] + m["vd"])
    return (2 * suffix * (_token_params(conf) - wkvb)
            + latent_prefill_flops(conf, suffix, prefix)
            + index_prefill_flops(conf, suffix, prefix)
            + 2 * m["d"] * m["V"])
