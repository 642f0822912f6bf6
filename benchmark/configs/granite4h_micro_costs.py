"""Operations and bytes the granite-4.0-h family NEEDS, from the
configuration FILE's published keys alone: lib/costs.py's questions
(weight_bytes, decode_bytes, decode_flops, prefill_flops,
page_bytes_all_layers, store_block_bytes, snapshot_bytes, same
signatures) answered for Mamba-2 layers with an attention layer where
`layer_types` says, a gated MLP in every layer, a tied embedding, and
two kinds of cache: K and V pages on the attention layers alone, and a
recurrent state a sequence that does not grow. Plus the state update's
and the chunked scan's own counts (`ssm_step_bytes`, `ssm_scan_flops`),
which the `ssm_*` metric readers divide device time into.

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs; prefill
needs the matmuls of the suffix tokens, causal attention over prefix +
suffix on the attention layers, the scan over the suffix on the state
layers, and the head for ONE position. The state is float32
(`assumed` in the configuration file), whatever the model's dtype: the
harness passes the MODEL's itemsize, and every state count here uses
STATE_ITEMSIZE.
"""

STATE_ITEMSIZE = 4


def _dims(conf):
    d = conf["hidden_size"]
    n_h, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    kinds = conf["layer_types"]
    return {
        "d": d, "n_h": n_h, "n_kv": n_kv, "hd": d // n_h,
        "ff": conf["shared_intermediate_size"], "V": conf["vocab_size"],
        "n_attn": sum(k == "attention" for k in kinds),
        "n_state": sum(k == "mamba" for k in kinds),
        "H": conf["mamba_n_heads"], "P": conf["mamba_d_head"],
        "N": conf["mamba_d_state"], "G": conf["mamba_n_groups"],
        "K": conf["mamba_d_conv"], "Q": conf["mamba_chunk_size"],
    }


def attn_params(conf):
    m = _dims(conf)
    return 2 * m["d"] * m["n_h"] * m["hd"] + 2 * m["d"] * m["n_kv"] * m["hd"]


def conv_dim(conf):
    m = _dims(conf)
    return m["H"] * m["P"] + 2 * m["G"] * m["N"]


def mixer_matmul_params(conf):
    """in_proj and out_proj of one Mamba-2 mixer."""
    m = _dims(conf)
    di = m["H"] * m["P"]
    return m["d"] * (di + conv_dim(conf) + m["H"]) + di * m["d"]


def mixer_params(conf):
    """... plus the convolution, its bias, A_log, dt_bias, D and the
    gated norm."""
    m = _dims(conf)
    return (mixer_matmul_params(conf) + conv_dim(conf) * (m["K"] + 1)
            + 3 * m["H"] + m["H"] * m["P"])


def mlp_params(conf):
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def param_count(conf):
    m = _dims(conf)
    layers = m["n_attn"] + m["n_state"]
    return (m["V"] * m["d"] + m["d"]                       # tied, final norm
            + layers * (mlp_params(conf) + 2 * m["d"])
            + m["n_attn"] * attn_params(conf)
            + m["n_state"] * mixer_params(conf))


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served: the model's dtype, A_log,
    dt_bias and D in float32."""
    m = _dims(conf)
    return param_count(conf) * itemsize \
        + m["n_state"] * 3 * m["H"] * (4 - itemsize)


def kv_bytes_per_token(conf, itemsize=2):
    m = _dims(conf)
    return 2 * m["n_attn"] * m["n_kv"] * m["hd"] * itemsize


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store: K and V
    of the attention layers alone."""
    return kv_bytes_per_token(conf, itemsize) * page


def store_block_bytes(conf, page=16, itemsize=2):
    """The smallest object an offload writes: one K or V page of one
    attention layer."""
    m = _dims(conf)
    return page * m["n_kv"] * m["hd"] * itemsize


def state_elems(conf):
    """Elements of ONE state layer's state for one sequence: h and the
    convolution's last K-1 inputs."""
    m = _dims(conf)
    return m["H"] * m["P"] * m["N"] + (m["K"] - 1) * conv_dim(conf)


def state_bytes(conf):
    """Bytes of one sequence's whole recurrent state."""
    return _dims(conf)["n_state"] * state_elems(conf) * STATE_ITEMSIZE


def snapshot_bytes(conf, itemsize=2, page=16):
    """Bytes an offload writes that do not grow with its pages: one
    snapshot, a row a state layer, each row rounded up to whole store
    blocks (serving._snapshot_row_elems). In the state's own dtype:
    `itemsize` is the model's and sizes only the block."""
    block = store_block_bytes(conf, page, itemsize)
    row = -(-state_elems(conf) * STATE_ITEMSIZE // block) * block
    return _dims(conf)["n_state"] * row


def ssm_step_bytes(conf, active, itemsize=2):
    """Bytes the state layers' mixers must move in one decode step:
    every active sequence's state read and written, and the mixers'
    weights read once."""
    m = _dims(conf)
    return 2 * active * state_bytes(conf) \
        + m["n_state"] * mixer_params(conf) * itemsize


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must move: the weights (the tied
    embedding once, as the head), one embedding row a token, the state
    of the active sequences read and written, and the live K/V."""
    m = _dims(conf)
    return (weight_bytes(conf, itemsize) + active * m["d"] * itemsize
            + 2 * active * state_bytes(conf)
            + live_tokens * kv_bytes_per_token(conf, itemsize))


def _matmul_params(conf):
    m = _dims(conf)
    layers = m["n_attn"] + m["n_state"]
    return (layers * mlp_params(conf) + m["n_attn"] * attn_params(conf)
            + m["n_state"] * mixer_matmul_params(conf))


def ssm_step_flops(conf, tokens):
    """The one-token recurrence of every state layer: decay, outer
    product, add, and the contraction with C (5 FLOPs a state
    element), the convolution and the D skip."""
    m = _dims(conf)
    per = 5 * m["H"] * m["P"] * m["N"] + 2 * m["K"] * conv_dim(conf) \
        + 2 * m["H"] * m["P"]
    return tokens * m["n_state"] * per


def ssm_scan_flops(conf, tokens):
    """The chunked scan of every state layer over `tokens` positions at
    the published chunk size: inside a chunk of q positions the causal
    half of C B^T and of its product with x (q (q + 1) / 2 pairs), the
    chunk's contribution to the state and the state's to the chunk's
    outputs (2 q N H P each), and the carry between chunks."""
    m = _dims(conf)
    hp = m["H"] * m["P"]
    flops = 0
    left = tokens
    while left > 0:
        q = min(m["Q"], left)
        pairs = q * (q + 1) // 2
        flops += 2 * pairs * (m["N"] + hp) + 4 * q * m["N"] * hp \
            + 2 * hp * m["N"]
        left -= q
    return m["n_state"] * flops


def decode_flops(conf, active, live_tokens):
    m = _dims(conf)
    return (2 * active * (_matmul_params(conf) + m["d"] * m["V"])
            + m["n_attn"] * m["n_h"] * live_tokens * 4 * m["hd"]
            + ssm_step_flops(conf, active))


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs needed to prefill `suffix` tokens over `prefix` cached
    ones (whose state arrives as a snapshot: the scan runs over the
    suffix alone)."""
    m = _dims(conf)
    pairs = suffix * prefix + suffix * (suffix + 1) // 2
    return (2 * suffix * _matmul_params(conf)
            + m["n_attn"] * m["n_h"] * pairs * 4 * m["hd"]
            + ssm_scan_flops(conf, suffix)
            + 2 * m["d"] * m["V"])
