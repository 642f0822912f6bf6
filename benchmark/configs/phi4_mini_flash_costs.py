"""Operations and bytes Phi-4-mini-flash's decoder-hybrid-decoder NEEDS,
from the configuration FILE's keys alone: lib/costs.py's questions
(weight_bytes, decode_bytes, decode_flops, prefill_flops,
page_bytes_all_layers, store_block_bytes, snapshot_bytes, same
signatures) answered for four kinds of layer (Mamba-1, differential
attention that owns K and V, Gated Memory Unit, cross-attention that
owns none), a gated MLP in every layer, a tied embedding, and THREE
kinds of cache: K and V pages of the banded layers (needed for the band
alone), of the ONE full layer (every position, read by that layer and
by every cross layer), and a recurrent state a sequence. Plus the
counts the per-stage readers divide device time into: `ssm_step_bytes`,
`ssm_scan_flops`, `window_attn_bytes`, `full_attn_bytes`,
`shared_kv_attn_bytes`.

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs. A pair of
differential heads needs its two score maps (2 x head_dim lanes each)
and ONE product of the subtracted map with its value of 2 head_dim
lanes: 4 head_dim multiply-adds a (query, key, pair), which is what a
plain head needs a (query, key, head) over twice the heads. An
admission that keeps ONE position's logits needs every layer up to the
last that owns a cache on every suffix row (and that layer's K and V
projections), and that layer's query, attention, output projection and
MLP, every layer above it and the head on ONE row: `prefill_flops`
counts what that cut LEAVES, so no share passes 100 % by counting rows
that were not run. The state is float32 (`assumed` in the file),
whatever the model's dtype.
"""

STATE_ITEMSIZE = 4


def layout(conf):
    """Per layer (kind, band), as `mb_per_layer`, `sliding_window` and
    the depth give it (the file's `assumed` says how)."""
    n = conf["num_hidden_layers"]
    half = n // 2
    out = []
    for l in range(n):
        mamba = l % conf["mb_per_layer"] == 0
        if l >= half + 2:
            out.append(("gmu" if mamba else "cross", 0))
        elif mamba:
            out.append(("mamba1", 0))
        else:
            out.append(("attention",
                        conf["sliding_window"] if l < half else 0))
    return out


def _dims(conf):
    d = conf["hidden_size"]
    n_h, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    kinds = layout(conf)
    return {
        "d": d, "n_h": n_h, "n_kv": n_kv, "hd": d // n_h,
        "ff": conf["intermediate_size"], "V": conf["vocab_size"],
        "L": len(kinds), "band": conf["sliding_window"],
        "n_mamba": sum(k == "mamba1" for k, _ in kinds),
        "n_window": sum(k == "attention" and b > 0 for k, b in kinds),
        "n_full": sum(k == "attention" and b == 0 for k, b in kinds),
        "n_gmu": sum(k == "gmu" for k, _ in kinds),
        "n_cross": sum(k == "cross" for k, _ in kinds),
        # the layers every row of an admission runs: up to the last
        # that owns a cache, that one not counted
        "below": max(i for i, (k, _) in enumerate(kinds)
                     if k in ("mamba1", "attention")),
        "C": conf["mamba_expand"] * d, "N": conf["mamba_d_state"],
        "K": conf["mamba_d_conv"], "R": conf["mamba_dt_rank"],
    }


def mamba_matmul_params(conf):
    """in_proj, x_proj, dt_proj and out_proj of one Mamba-1 mixer."""
    m = _dims(conf)
    return (m["d"] * 2 * m["C"] + m["C"] * (m["R"] + 2 * m["N"])
            + m["R"] * m["C"] + m["C"] * m["d"])


def mamba_f32_params(conf):
    """dt_bias, A_log and D, held in float32."""
    m = _dims(conf)
    return m["C"] * (2 + m["N"])


def mamba_params(conf):
    """... plus the convolution and its bias."""
    m = _dims(conf)
    return (mamba_matmul_params(conf) + m["C"] * (m["K"] + 1)
            + mamba_f32_params(conf))


def q_o_matmul_params(conf):
    m = _dims(conf)
    return 2 * m["d"] * m["n_h"] * m["hd"]


def kv_matmul_params(conf):
    m = _dims(conf)
    return 2 * m["d"] * m["n_kv"] * m["hd"]


def diff_params(conf):
    """The 2 head_dim norm weight and the four lambda vectors (float32)
    of one differential attention or cross layer."""
    m = _dims(conf)
    return 2 * m["hd"], 4 * m["hd"]


def cross_params(conf):
    """W_q and W_o with their biases, the norm weight, the lambdas."""
    m = _dims(conf)
    norm_w, lam = diff_params(conf)
    return (q_o_matmul_params(conf) + m["n_h"] * m["hd"] + m["d"]
            + norm_w + lam)


def attn_params(conf):
    """... plus W_k, W_v and their biases."""
    m = _dims(conf)
    return (cross_params(conf) + kv_matmul_params(conf)
            + 2 * m["n_kv"] * m["hd"])


def gmu_params(conf):
    m = _dims(conf)
    return 2 * m["d"] * m["C"]


def mlp_params(conf):
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def param_count(conf):
    m = _dims(conf)
    return (m["V"] * m["d"] + 2 * m["d"]              # tied, final LN + bias
            + m["L"] * (mlp_params(conf) + 4 * m["d"])  # two LNs with bias
            + m["n_mamba"] * mamba_params(conf)
            + (m["n_window"] + m["n_full"]) * attn_params(conf)
            + m["n_gmu"] * gmu_params(conf)
            + m["n_cross"] * cross_params(conf))


def weight_bytes(conf, itemsize=2):
    """Bytes of the weights as served: the model's dtype, the Mamba
    layers' dt_bias, A_log and D and every lambda vector in float32."""
    m = _dims(conf)
    f32 = m["n_mamba"] * mamba_f32_params(conf) + (
        m["n_window"] + m["n_full"] + m["n_cross"]) * diff_params(conf)[1]
    return param_count(conf) * itemsize + f32 * (4 - itemsize)


def kv_bytes_per_token_layer(conf, itemsize=2):
    """K and V of one token in one layer that owns them."""
    m = _dims(conf)
    return 2 * m["n_kv"] * m["hd"] * itemsize


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """Cache bytes one full page of tokens adds to the store: every
    full page of every layer that owns K and V, banded or full, is
    written once."""
    m = _dims(conf)
    return (m["n_window"] + m["n_full"]) * page \
        * kv_bytes_per_token_layer(conf, itemsize)


def store_block_bytes(conf, page=16, itemsize=2):
    """The store's allocation unit. The smallest object an offload
    writes is one K or V page of one layer, 40 KB at the published
    widths (16 x 10 rows of 128 lanes), which is no power of two; the
    store wants one, so the unit is the largest that divides a page."""
    one = page * kv_bytes_per_token_layer(conf, itemsize) // 2
    return one & -one


def state_elems(conf):
    """Elements of ONE Mamba layer's state for one sequence: the state
    [N, C] and the convolution's last K-1 inputs."""
    m = _dims(conf)
    return m["C"] * (m["N"] + m["K"] - 1)


def state_bytes(conf):
    """Bytes of one sequence's whole recurrent state."""
    return _dims(conf)["n_mamba"] * state_elems(conf) * STATE_ITEMSIZE


def snapshot_bytes(conf, itemsize=2, page=16):
    """Bytes an offload writes that do not grow with its pages: one
    snapshot, a row a Mamba layer, each row rounded up to whole K pages
    (serving._snapshot_row_elems)."""
    one = page * kv_bytes_per_token_layer(conf, itemsize) // 2
    row = -(-state_elems(conf) * STATE_ITEMSIZE // one) * one
    return _dims(conf)["n_mamba"] * row


def _band_tokens(conf, active, live_tokens):
    """Tokens of `live_tokens` (over `active` sequences) that a banded
    layer's step attends: each sequence's last `band`."""
    return min(live_tokens, active * _dims(conf)["band"])


def full_attn_bytes(conf, active, live_tokens, itemsize=2):
    """K and V the layer that OWNS the whole-context cache must read
    in one decode step."""
    m = _dims(conf)
    return m["n_full"] * live_tokens * kv_bytes_per_token_layer(
        conf, itemsize)


def shared_kv_attn_bytes(conf, active, live_tokens, itemsize=2):
    """... and the cross layers, which read that same layer's K and V
    once each."""
    m = _dims(conf)
    return m["n_cross"] * live_tokens * kv_bytes_per_token_layer(
        conf, itemsize)


def window_attn_bytes(conf, active, live_tokens, itemsize=2):
    m = _dims(conf)
    return m["n_window"] * _band_tokens(conf, active, live_tokens) \
        * kv_bytes_per_token_layer(conf, itemsize)


def ssm_step_bytes(conf, active, itemsize=2):
    """Bytes the Mamba mixers and the Gated Memory Units must move in
    one decode step: every active sequence's state read and written,
    their weights read once. (The GMUs' operations carry the `ssm.gmu`
    scope, which `ssm_step_roofline_share` leaves on the mixers' side
    of its subtraction.)"""
    m = _dims(conf)
    weights = (m["n_mamba"] * (mamba_params(conf) - mamba_f32_params(conf))
               + m["n_gmu"] * gmu_params(conf)) * itemsize \
        + m["n_mamba"] * mamba_f32_params(conf) * 4
    return 2 * active * state_bytes(conf) + weights


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must move: the weights once (the tied
    embedding as the head), one embedding row a token, the state of the
    active sequences read and written, the banded layers' band once
    each, and the ONE whole-context cache once for every layer that
    attends it (its owner and the cross layers)."""
    m = _dims(conf)
    return (weight_bytes(conf, itemsize) + active * m["d"] * itemsize
            + 2 * active * state_bytes(conf)
            + window_attn_bytes(conf, active, live_tokens, itemsize)
            + full_attn_bytes(conf, active, live_tokens, itemsize)
            + shared_kv_attn_bytes(conf, active, live_tokens, itemsize))


def ssm_scan_flops(conf, tokens):
    """The selective scan of every Mamba layer over `tokens` positions:
    per state element and position the decay's argument, the decay's
    product with the state, the input's outer product, the add, the
    product with C and its sum (6 FLOPs; the exponential not counted).
    Vector-unit work throughout: against the bf16 matrix peak it reads
    low by construction."""
    m = _dims(conf)
    return tokens * m["n_mamba"] * 6 * m["N"] * m["C"]


def _attention_flops(conf, pairs):
    """Differential attention over `pairs` (query, key) pairs in one
    layer: 4 head_dim multiply-adds a pair of heads (module docstring)
    = 4 head_dim FLOPs a (query, key, head)."""
    m = _dims(conf)
    return m["n_h"] * pairs * 4 * m["hd"]


def banded_pairs(conf, suffix, prefix=0):
    """(query, key) pairs of `suffix` queries over `prefix` + suffix
    keys inside the band."""
    band = _dims(conf)["band"]
    return sum(min(prefix + i + 1, band) for i in range(suffix))


def _every_row_params(conf):
    """Matmul parameters every suffix row of an admission runs: the
    layers below the last cache, and that layer's K and V."""
    m = _dims(conf)
    return (m["below"] * mlp_params(conf)
            + m["n_mamba"] * mamba_matmul_params(conf)
            + m["n_window"] * (q_o_matmul_params(conf)
                               + kv_matmul_params(conf))
            + m["n_full"] * kv_matmul_params(conf))


def _one_row_params(conf):
    """... and those the kept row alone runs: the last cache's query,
    output projection and MLP, every layer above it."""
    m = _dims(conf)
    return ((m["L"] - m["below"]) * mlp_params(conf)
            + (m["n_full"] + m["n_cross"]) * q_o_matmul_params(conf)
            + m["n_gmu"] * gmu_params(conf))


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs needed to prefill `suffix` tokens over `prefix` cached
    ones and keep the last position's logits (the state arrives as a
    snapshot: the scan runs over the suffix alone)."""
    m = _dims(conf)
    return (2 * suffix * _every_row_params(conf)
            + 2 * _one_row_params(conf)
            + m["n_window"] * _attention_flops(
                conf, banded_pairs(conf, suffix, prefix))
            + (m["n_full"] + m["n_cross"]) * _attention_flops(
                conf, prefix + suffix)
            + ssm_scan_flops(conf, suffix)
            + 2 * m["d"] * m["V"])


def decode_flops(conf, active, live_tokens):
    m = _dims(conf)
    return (2 * active * (_every_row_params(conf) + _one_row_params(conf)
                          + m["d"] * m["V"])
            + (m["n_full"] + m["n_cross"]) * _attention_flops(
                conf, live_tokens)
            + m["n_window"] * _attention_flops(
                conf, _band_tokens(conf, active, live_tokens))
            + ssm_scan_flops(conf, active))
