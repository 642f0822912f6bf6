"""Operations and bytes the SmallThinker family NEEDS, from the
configuration FILE's published keys alone: lib/costs.py's questions
(weight_bytes, decode_bytes, decode_flops, prefill_flops,
page_bytes_all_layers, store_block_bytes, snapshot_bytes, same
signatures) answered for full and banded attention layers
(`sliding_window_layout`) and `moe_num_primary_experts` ReGLU experts of
which a token uses `moe_num_active_primary_experts`. Plus the counts
this family's own readers divide device time into: the K and V a
decode step must read of each kind of layer (`full_attn_bytes`,
`window_attn_bytes`), the experts' FLOPs of a prefill
(`moe_prefill_flops`) and the experts' bytes of a decode step
(`moe_step_bytes`).

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs; prefill
needs the matmuls of the suffix tokens with the chosen experts only,
attention over the keys a query may see (a banded layer's query at most
`sliding_window_size` of them), and the head for ONE position. Every
need is a lower bound on what the stage must move.

The harness hands `decode_bytes` the SUM of the active sequences' live
tokens. A banded layer reads min(sequence, band) tokens a sequence,
which the sum does not determine; min(sum, active x band) is exact
where every sequence is at least a band long (every sequence of the
cell's traffic is) and an upper bound on the need otherwise.
"""


def _dims(conf):
    banded = conf["sliding_window_layout"]
    return {
        "d": conf["hidden_size"], "n_h": conf["num_attention_heads"],
        "n_kv": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "ff": conf["moe_ffn_hidden_size"], "V": conf["vocab_size"],
        "E": conf["moe_num_primary_experts"],
        "k": conf["moe_num_active_primary_experts"],
        "L": conf["num_hidden_layers"], "W": conf["sliding_window_size"],
        "n_win": sum(1 for b in banded if b),
        "n_full": sum(1 for b in banded if not b),
    }


def attn_params(conf):
    m = _dims(conf)
    return 2 * m["d"] * m["n_h"] * m["hd"] + 2 * m["d"] * m["n_kv"] * m["hd"]


def expert_params(conf):
    """One ReGLU expert (gate, up, down)."""
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def router_params(conf):
    m = _dims(conf)
    return m["d"] * m["E"]


def param_count(conf):
    """All parameters held: embedding, untied head, norms, layers."""
    m = _dims(conf)
    per_layer = (attn_params(conf) + m["E"] * expert_params(conf)
                 + router_params(conf) + 2 * m["d"])
    return 2 * m["V"] * m["d"] + m["d"] + m["L"] * per_layer


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served (the router is float32)."""
    m = _dims(conf)
    return param_count(conf) * itemsize \
        + m["L"] * router_params(conf) * (4 - itemsize)


def kv_bytes_per_token_layer(conf, itemsize=2):
    m = _dims(conf)
    return 2 * m["n_kv"] * m["hd"] * itemsize


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store: what an
    offload WRITES, K and V of every layer of both kinds."""
    return _dims(conf)["L"] * kv_bytes_per_token_layer(conf, itemsize) * page


def store_block_bytes(conf, page=16, itemsize=2):
    """The smallest object an offload writes: one K or V page of one
    layer."""
    return kv_bytes_per_token_layer(conf, itemsize) // 2 * page


def snapshot_bytes(conf, itemsize=2):
    return 0


def expected_experts_touched(conf, tokens):
    """Expected distinct experts `tokens` tokens touch when each picks
    k of E uniformly: E (1 - (1 - k/E) ** tokens)."""
    m = _dims(conf)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** max(0, tokens))


def full_attn_bytes(conf, active, live_tokens, itemsize=2):
    """K and V the full layers' attention must read in one decode
    step: every live token of the active sequences."""
    return _dims(conf)["n_full"] * live_tokens \
        * kv_bytes_per_token_layer(conf, itemsize)


def window_attn_bytes(conf, active, live_tokens, itemsize=2):
    """... and the banded layers': the band of each (module
    docstring)."""
    m = _dims(conf)
    return m["n_win"] * min(live_tokens, active * m["W"]) \
        * kv_bytes_per_token_layer(conf, itemsize)


def moe_step_bytes(conf, active, itemsize=2):
    """Bytes the expert blocks must read in one decode step: the
    experts `active` tokens touch in expectation, and the routers."""
    m = _dims(conf)
    return m["L"] * (expected_experts_touched(conf, active)
                     * expert_params(conf) * itemsize
                     + router_params(conf) * 4)


def moe_prefill_flops(conf, tokens):
    """FLOPs the expert blocks need for `tokens` prefilled tokens: k
    experts a token and the router."""
    m = _dims(conf)
    return 2 * tokens * m["L"] * (m["k"] * expert_params(conf)
                                  + router_params(conf))


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must read: attention weights and norms,
    the experts touched and the routers, the head, one embedding row a
    token, and the K and V each kind of layer attends."""
    m = _dims(conf)
    weights = m["L"] * (attn_params(conf) + 2 * m["d"]) * itemsize \
        + moe_step_bytes(conf, active, itemsize) \
        + (m["V"] * m["d"] + m["d"]) * itemsize + active * m["d"] * itemsize
    return weights + full_attn_bytes(conf, active, live_tokens, itemsize) \
        + window_attn_bytes(conf, active, live_tokens, itemsize)


def _active_params(conf):
    m = _dims(conf)
    return attn_params(conf) + m["k"] * expert_params(conf) \
        + router_params(conf)


def decode_flops(conf, active, live_tokens):
    m = _dims(conf)
    attended = m["n_full"] * live_tokens \
        + m["n_win"] * min(live_tokens, active * m["W"])
    return 2 * active * (m["L"] * _active_params(conf) + m["d"] * m["V"]) \
        + m["n_h"] * attended * 4 * m["hd"]


def banded_pairs(conf, suffix, prefix=0):
    """(query, key) pairs of one banded layer: query i of the suffix
    sits at position prefix + i and sees min(prefix + i + 1, W) keys."""
    w = _dims(conf)["W"]
    ramp = max(0, min(suffix, w - prefix - 1))   # queries that see < W keys
    first = prefix + 1
    return ramp * (2 * first + ramp - 1) // 2 + (suffix - ramp) * w


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs needed to prefill `suffix` tokens over `prefix` cached
    ones."""
    m = _dims(conf)
    full_pairs = suffix * prefix + suffix * (suffix + 1) // 2
    pairs = m["n_full"] * full_pairs \
        + m["n_win"] * banded_pairs(conf, suffix, prefix)
    return (2 * suffix * m["L"] * _active_params(conf)
            + m["n_h"] * pairs * 4 * m["hd"] + 2 * m["d"] * m["V"])
