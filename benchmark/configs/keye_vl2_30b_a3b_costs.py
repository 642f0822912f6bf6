"""Operations and bytes the language model of Keye-VL-2.0-30B-A3B NEEDS,
from the configuration FILE's keys alone: lib/costs.py's questions
(weight_bytes, decode_bytes, decode_flops, prefill_flops,
page_bytes_all_layers, store_block_bytes, snapshot_bytes, same
signatures) answered for grouped-query attention under a learned
selection (`sa_config.topk` rows a query) with an indexer on EVERY
layer and `num_experts` softmax-routed experts, all held. Plus the
counts this family's readers divide device time into: the selected K
and V rows a decode step must read (`sparse_attn_bytes`), the index
keys and indexer weights it must read (`index_score_bytes`), the index
scores' FLOPs of an admission (`index_prefill_flops`), its attention's
over the pairs the selection LEAVES (`sparse_prefill_flops`), the
experts' FLOPs of a prefill (`moe_prefill_flops`) and bytes of a decode
step (`moe_step_bytes`).

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs; every need
is a lower bound on what the stage must move and knows nothing of the
implementation. What the SELECTION leaves is what is counted: a query
attends min(keys it may see, topk) rows; a selected row needs a K and
a V row of num_key_value_heads x head_dim values a layer (2 x 512 x
2 B = 2,048 B), read once for the 8 query heads of a group; a live
token's index key indexer_head_dim values (128 B) in every layer; a
causal pair 2 x indexer_num_heads x indexer_head_dim FLOPs (2,048) a
layer. An admission that attends every row of prefix and suffix under
a mask does 17 x the attention FLOPs counted here at 35k rows: that is
waste, not need, and shows as a low `sparse_prefill_mfu`. The one place
the program's layout shows is what the STORE holds
(`page_bytes_all_layers`, `store_block_bytes`): an index key is stored
as it lies in the pool, 128 lanes of which 64 are zero.

The harness hands `decode_bytes` the SUM of the active sequences' live
tokens; a sequence reads min(its length, topk) rows, which the sum does
not determine: `selected_rows` is the LEAST the sum allows (min(sum,
active x topk)), exact where every active sequence is past topk, as in
this configuration's cell.
"""

LANES = 128


def _dims(conf):
    sa = conf["sa_config"]
    return {
        "d": conf["hidden_size"], "H": conf["num_attention_heads"],
        "G": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "ff": conf["moe_intermediate_size"], "E": conf["num_experts"],
        "k": conf["num_experts_per_tok"], "L": conf["num_hidden_layers"],
        "V": conf["vocab_size"], "Hi": sa["indexer_num_heads"],
        "Di": sa["indexer_head_dim"], "topk": sa["topk"],
    }


def attn_params(conf):
    """Wq, Wk, Wv, Wo and the q and k norms."""
    m = _dims(conf)
    return (2 * m["d"] * m["H"] * m["hd"] + 2 * m["d"] * m["G"] * m["hd"]
            + 2 * m["hd"])


def indexer_params(conf):
    """WqI, WkI, Ww and the key LayerNorm's weight and bias."""
    m = _dims(conf)
    return (m["d"] * m["Hi"] * m["Di"] + m["d"] * m["Di"]
            + m["d"] * m["Hi"] + 2 * m["Di"])


def expert_params(conf):
    """One SwiGLU expert (gate, up, down)."""
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def router_params(conf):
    m = _dims(conf)
    return m["d"] * m["E"]


def layer_params(conf):
    m = _dims(conf)
    return (attn_params(conf) + indexer_params(conf) + router_params(conf)
            + m["E"] * expert_params(conf) + 2 * m["d"])


def param_count(conf):
    """All parameters held: embedding, untied head, final norm,
    layers."""
    m = _dims(conf)
    return 2 * m["V"] * m["d"] + m["d"] + m["L"] * layer_params(conf)


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served (the routers are float32)."""
    m = _dims(conf)
    return param_count(conf) * itemsize \
        + m["L"] * router_params(conf) * (4 - itemsize)


def kv_values(conf):
    """Values a cached token needs a layer in K and V."""
    m = _dims(conf)
    return 2 * m["G"] * m["hd"]


def stored_index_values(conf):
    """An index key as the store holds it: whole lane tiles."""
    return -(-_dims(conf)["Di"] // LANES) * LANES


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store: a K, a V
    and an index page of every layer (5 x (16,384 + 16,384 + 4,096) =
    184,320 B)."""
    m = _dims(conf)
    return m["L"] * (kv_values(conf) + stored_index_values(conf)) \
        * itemsize * page


def store_block_bytes(conf, page=16, itemsize=2):
    """The store's allocation unit: the largest power of two that
    divides every object an offload writes, a K or V page (16,384 B)
    and an index page (4,096 B): 4 KB."""
    kv = kv_values(conf) // 2 * itemsize * page
    index = stored_index_values(conf) * itemsize * page
    return min(kv & -kv, index & -index)


def snapshot_bytes(conf, itemsize=2):
    return 0


def expected_experts_touched(conf, tokens):
    """Expected distinct experts `tokens` tokens touch when each picks
    k of E evenly: E (1 - (1 - k/E) ** tokens)."""
    m = _dims(conf)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** max(0, tokens))


def selected_rows(conf, active, live_tokens):
    """Rows the attention of one layer reads in a decode step: each
    active sequence's min(length, topk), at the least the sum
    `live_tokens` allows (module docstring)."""
    return min(live_tokens, active * _dims(conf)["topk"])


def sparse_attn_bytes(conf, active, live_tokens, itemsize=2):
    """Cache rows the attention of one decode step must read: the
    selected K and V rows of the active sequences, 2 x 512 values a
    layer, once for the query heads of a group."""
    return _dims(conf)["L"] * selected_rows(conf, active, live_tokens) \
        * kv_values(conf) * itemsize


def index_score_bytes(conf, active, live_tokens, itemsize=2):
    """What the indexers of one decode step must read: every live
    token's index key in every layer, and the layers' indexer
    weights."""
    m = _dims(conf)
    return m["L"] * (live_tokens * m["Di"] + indexer_params(conf)) * itemsize


def causal_pairs(suffix, prefix=0):
    return suffix * prefix + suffix * (suffix + 1) // 2


def selects(conf, suffix, prefix=0):
    """Whether an admission's queries see more keys than topk (else
    every row is selected and no score is needed)."""
    return prefix + suffix > _dims(conf)["topk"]


def selected_pairs(conf, suffix, prefix=0):
    """(query, attended row) pairs of an admission under the selection:
    query i of the suffix attends min(prefix + i + 1, topk)."""
    k = _dims(conf)["topk"]
    below = max(0, min(suffix, k - prefix))  # queries that see <= k keys
    return causal_pairs(below, prefix) + (suffix - below) * k


def index_prefill_flops(conf, suffix, prefix=0):
    """FLOPs the index scores of an admission need: every causal pair
    of every layer, 2 x Hi x Di each; none where every row is
    selected."""
    if not selects(conf, suffix, prefix):
        return 0
    m = _dims(conf)
    return m["L"] * causal_pairs(suffix, prefix) * 2 * m["Hi"] * m["Di"]


def sparse_prefill_flops(conf, suffix, prefix=0):
    """FLOPs the attention of an admission needs over the pairs the
    selection leaves: scores and weighted values, 4 x head_dim a query
    head a pair a layer."""
    m = _dims(conf)
    return m["L"] * selected_pairs(conf, suffix, prefix) * m["H"] \
        * 4 * m["hd"]


def moe_step_bytes(conf, active, itemsize=2):
    """Bytes the expert blocks must read in one decode step: the
    experts `active` tokens touch in expectation and the routers."""
    m = _dims(conf)
    return m["L"] * (expected_experts_touched(conf, active)
                     * expert_params(conf) * itemsize
                     + router_params(conf) * 4)


def moe_prefill_flops(conf, tokens):
    """FLOPs the expert blocks need for `tokens` prefilled tokens: the
    k chosen experts and the router."""
    m = _dims(conf)
    return 2 * tokens * m["L"] * (m["k"] * expert_params(conf)
                                  + router_params(conf))


def _token_params(conf):
    """Parameters one token's matmuls touch outside attention's pairs
    and the index scores: every layer's attention and indexer
    projections, its chosen experts and its router."""
    m = _dims(conf)
    return m["L"] * (attn_params(conf) + indexer_params(conf)
                     + m["k"] * expert_params(conf) + router_params(conf))


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must read: attention weights and norms of
    every layer, the experts touched and the routers, the head, one
    embedding row a token, and of the cache what the selection leaves:
    the selected K and V rows and the index keys (with the indexers'
    weights)."""
    m = _dims(conf)
    weights = (m["L"] * (attn_params(conf) + 2 * m["d"]) * itemsize
               + moe_step_bytes(conf, active, itemsize)
               + (m["V"] * m["d"] + m["d"]) * itemsize
               + active * m["d"] * itemsize)
    return weights + sparse_attn_bytes(conf, active, live_tokens, itemsize) \
        + index_score_bytes(conf, active, live_tokens, itemsize)


def decode_flops(conf, active, live_tokens):
    """... and its FLOPs: a token's matmuls, the head, the index
    scores over the live keys, and scores and weighted values over the
    selected rows."""
    m = _dims(conf)
    return (2 * active * (_token_params(conf) + m["d"] * m["V"])
            + m["L"] * live_tokens * 2 * m["Hi"] * m["Di"]
            + m["L"] * m["H"] * selected_rows(conf, active, live_tokens)
            * 4 * m["hd"])


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs needed to prefill `suffix` tokens over `prefix` cached
    ones; the head for ONE position."""
    m = _dims(conf)
    return (2 * suffix * _token_params(conf)
            + sparse_prefill_flops(conf, suffix, prefix)
            + index_prefill_flops(conf, suffix, prefix)
            + 2 * m["d"] * m["V"])
