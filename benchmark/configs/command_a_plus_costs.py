"""Operations and bytes the Command A+ family NEEDS as ONE CHIP'S SHARE
of it, from the configuration FILE's keys alone: lib/costs.py's
questions (weight_bytes, decode_bytes, decode_flops, prefill_flops,
page_bytes_all_layers, store_block_bytes, snapshot_bytes, same
signatures) answered for window and full attention layers
(`layer_types`), `num_experts` routed experts HELD HERE of the
`expert_share.router_width` the router scores, `num_experts_per_tok`
chosen a token, and `num_shared_experts` shared experts every token
goes through. Plus the counts this family's readers divide device time
into, under the names smallthinker21b_costs.py gives them: the K and V
a decode step must read of each kind of layer (`full_attn_bytes`,
`window_attn_bytes`), the experts' FLOPs of a prefill
(`moe_prefill_flops`) and the experts' bytes of a decode step
(`moe_step_bytes`).

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs; a prefill
needs the matmuls of the suffix tokens with the chosen experts HELD
HERE only (k x held / width a token in expectation: one, at 8 of 128
with 16 held), the shared experts, attention over the keys a query may
see, and the head for ONE position. Every need is a lower bound on what
the stage must move.

The harness hands `decode_bytes` the SUM of the active sequences' live
tokens. A window layer reads min(sequence, window) tokens a sequence,
which the sum does not determine, and this cell holds sequences under
the window beside ones past it; `window_tokens` is the LEAST the sum
allows (as many sequences as the sum can fill to the longest a slot
may hold read a whole window each, the rest of the sum at most one
window), so the banded share reads low, never high.
"""


def _dims(conf):
    kinds = conf["layer_types"]
    share = conf.get("expert_share") or {}
    serving = conf.get("serving", {})
    return {
        "d": conf["hidden_size"], "n_h": conf["num_attention_heads"],
        "n_kv": conf["num_key_value_heads"], "hd": conf["head_dim"],
        "ff": conf["intermediate_size"], "V": conf["vocab_size"],
        "E": conf["num_experts"],
        "R": share.get("router_width", conf["num_experts"]),
        "k": conf["num_experts_per_tok"],
        "n_s": conf.get("num_shared_experts", 0),
        "L": conf["num_hidden_layers"], "W": conf["sliding_window"],
        "n_win": sum(1 for t in kinds if t == "sliding_attention"),
        "n_full": sum(1 for t in kinds if t == "full_attention"),
        "longest": serving.get("max_pages_per_seq", 0)
        * serving.get("page_size", 16),
    }


def attn_params(conf):
    m = _dims(conf)
    return 2 * m["d"] * m["n_h"] * m["hd"] + 2 * m["d"] * m["n_kv"] * m["hd"]


def expert_params(conf):
    """One gated expert (gate, up, down), routed or shared."""
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def shared_params(conf):
    return _dims(conf)["n_s"] * expert_params(conf)


def router_params(conf):
    """The router keeps its published width whatever is held here."""
    m = _dims(conf)
    return m["d"] * m["R"]


def layer_params(conf):
    m = _dims(conf)
    return (attn_params(conf) + shared_params(conf) + router_params(conf)
            + m["d"] + m["E"] * expert_params(conf))


def param_count(conf):
    """All parameters held: the vocabulary's rows held here (embedding
    and head are one), the final norm, the layers."""
    m = _dims(conf)
    return m["V"] * m["d"] + m["d"] + m["L"] * layer_params(conf)


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served (the router is float32)."""
    m = _dims(conf)
    return param_count(conf) * itemsize \
        + m["L"] * router_params(conf) * (4 - itemsize)


def kv_bytes_per_token_layer(conf, itemsize=2):
    m = _dims(conf)
    return 2 * m["n_kv"] * m["hd"] * itemsize


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store: K and V
    of every layer of both kinds."""
    return _dims(conf)["L"] * kv_bytes_per_token_layer(conf, itemsize) * page


def store_block_bytes(conf, page=16, itemsize=2):
    """The smallest object an offload writes: one K or V page of one
    layer."""
    return kv_bytes_per_token_layer(conf, itemsize) // 2 * page


def snapshot_bytes(conf, itemsize=2):
    return 0


def held_pairs_per_token(conf):
    """(token, chosen expert) pairs of one token that fall on experts
    held here, in expectation under even routing: k x held / width."""
    m = _dims(conf)
    return m["k"] * m["E"] / m["R"]


def expected_experts_touched(conf, tokens):
    """Expected distinct HELD experts `tokens` tokens touch when each
    picks k of the router's R evenly: E (1 - (1 - k/R) ** tokens)."""
    m = _dims(conf)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["R"]) ** max(0, tokens))


def window_tokens(conf, active, live_tokens):
    """The least tokens the window layers can have to read of `active`
    sequences whose lengths sum to `live_tokens` (module docstring)."""
    m = _dims(conf)
    longest = max(m["longest"], m["W"])
    full = min(active, live_tokens // longest)
    rest = live_tokens - full * longest
    return full * m["W"] + (min(rest, m["W"]) if active > full else 0)


def full_attn_bytes(conf, active, live_tokens, itemsize=2):
    """K and V the full layers' attention must read in one decode
    step: every live token of the active sequences."""
    return _dims(conf)["n_full"] * live_tokens \
        * kv_bytes_per_token_layer(conf, itemsize)


def window_attn_bytes(conf, active, live_tokens, itemsize=2):
    """... and the window layers', at the least."""
    return _dims(conf)["n_win"] * window_tokens(conf, active, live_tokens) \
        * kv_bytes_per_token_layer(conf, itemsize)


def moe_step_bytes(conf, active, itemsize=2):
    """Bytes the expert blocks must read in one decode step: the held
    experts `active` tokens touch in expectation, the shared experts
    and the routers."""
    m = _dims(conf)
    return m["L"] * ((expected_experts_touched(conf, active)
                      * expert_params(conf) + shared_params(conf))
                     * itemsize + router_params(conf) * 4)


def _block_params_per_token(conf):
    """Parameters of the experts' half a token multiplies with, in
    expectation: the held pairs, the shared experts, the router."""
    return (held_pairs_per_token(conf) * expert_params(conf)
            + shared_params(conf) + router_params(conf))


def moe_prefill_flops(conf, tokens):
    """FLOPs the expert blocks need for `tokens` prefilled tokens."""
    return 2 * tokens * _dims(conf)["L"] * _block_params_per_token(conf)


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must read: attention weights and norms,
    the experts' half, the head (= the embedding's rows held), and the
    K and V each kind of layer attends."""
    m = _dims(conf)
    weights = m["L"] * (attn_params(conf) + m["d"]) * itemsize \
        + moe_step_bytes(conf, active, itemsize) \
        + (m["V"] * m["d"] + m["d"]) * itemsize
    return weights + full_attn_bytes(conf, active, live_tokens, itemsize) \
        + window_attn_bytes(conf, active, live_tokens, itemsize)


def _active_params(conf):
    return attn_params(conf) + _block_params_per_token(conf)


def decode_flops(conf, active, live_tokens):
    m = _dims(conf)
    attended = m["n_full"] * live_tokens \
        + m["n_win"] * window_tokens(conf, active, live_tokens)
    return 2 * active * (m["L"] * _active_params(conf) + m["d"] * m["V"]) \
        + m["n_h"] * attended * 4 * m["hd"]


def banded_pairs(conf, suffix, prefix=0):
    """(query, key) pairs of one window layer: query i of the suffix
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
