"""Operations and bytes the EvaByte family NEEDS, from the configuration
FILE's published keys alone: lib/costs.py's questions (weight_bytes,
decode_bytes, decode_flops, prefill_flops, page_bytes_all_layers,
store_block_bytes, snapshot_bytes, same signatures) answered for a
dense decoder of 32 / 32 heads whose cache rows are NOT positions: a
window of `window_size` positions is attended exactly while a sequence
is in it and as `window_size // chunk_size` summary rows from then on
(benchmark/reference/evabyte_eva.py has the equations). Plus the counts
this family's own readers divide device time into: the cache rows a
decode step's attention reads (`folded_attn_bytes`) and what one fold
moves (`fold_bytes`).

Conventions as in lib/costs.py: a multiply-add is 2 FLOPs; every need
is a lower bound. The harness hands `decode_bytes` POSITIONS
(lib/serve.py StepSpans sums `seq_len`), and a sequence at position p
in window w holds 128 w + (p - 2048 w) rows, anything from p / 16 (at
a window's start) to p / 16 + 1,920 (at its end); `decode_bytes`
counts the LEAST a step at those positions can read, p / 16 + 1 rows a
sequence, so `decode_roofline_share` reads a floor here and no share
passes 100 % by counting rows that were folded away. What the step
DID read is the engine's own count (`cache_rows` on its decode spans),
which `folded_attn_roofline_share` takes.
"""


def _dims(conf):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return {"d": d, "H": h, "hd": d // h, "ff": conf["intermediate_size"],
            "L": conf["num_hidden_layers"], "V": conf["vocab_size"],
            "heads": conf["num_pred_heads"], "W": conf["window_size"],
            "c": conf["chunk_size"]}


def attn_params(conf):
    """Wq, Wk, Wv, Wo (32 kv heads: all four are d x d) and the
    summariser's phi and mu, a head each."""
    m = _dims(conf)
    return 4 * m["d"] * m["d"] + 2 * m["H"] * m["hd"]


def mlp_params(conf):
    m = _dims(conf)
    return 3 * m["d"] * m["ff"]


def layer_params(conf):
    return attn_params(conf) + mlp_params(conf) + 2 * conf["hidden_size"]


def param_count(conf):
    """All parameters held: the byte embedding, the head of
    `num_pred_heads` x `vocab_size` logits, the final norm, layers."""
    m = _dims(conf)
    return (m["V"] * m["d"] + m["d"] * m["heads"] * m["V"] + m["d"]
            + m["L"] * layer_params(conf))


def weight_bytes(conf, itemsize=2):
    return param_count(conf) * itemsize


def row_bytes(conf, itemsize=2):
    """One cache row of one layer, K and V of every head: 16,384 B."""
    m = _dims(conf)
    return 2 * m["H"] * m["hd"] * itemsize


def full_page_bytes(conf, page=16, itemsize=2):
    """One pool page over every layer, K and V: 3,145,728 B at 12
    layers."""
    return _dims(conf)["L"] * row_bytes(conf, itemsize) * page


def page_bytes_all_layers(conf, page=16, itemsize=2):
    """What a page of TOKENS puts in the store at most. lib/cell.py
    sizes the store's pool from lib/traffic.py's count of the pages of
    positions a session's finishes hold beyond their hits; this family
    writes, of such a counted page, its sixteenth of a summary page (a
    summary page of 16 rows stands for 16 pages of positions) and, only
    for the window a finish ends in, the exact page itself. By hand
    over `docs24k-bytes`' four classes (tests/benchmark/
    test_bench_evabyte.py holds it): 972, 1,116, 1,836 and 1,788
    counted pages a session against 242, 281, 249 and 354 pool pages
    really written (56-120 summary pages + 186-250 exact), 0.43-0.79
    MB a counted page, 885 MB a session in the mean. A quarter of a
    pool page (786,432 B: the sixteenth, and three sixteenths for the
    exact band) covers the mean with room: 1,428 counted pages x 0.79
    MB = 1.12 GB a session, a pool of 8.5 GB at 0.2 sessions/s over 40
    s."""
    return full_page_bytes(conf, page, itemsize) // 4


def store_block_bytes(conf, page=16, itemsize=2):
    """The store's allocation unit: one K (or V) page of one layer,
    summary or exact alike, 16 x 32 x 128 x 2 B = 128 KiB."""
    return row_bytes(conf, itemsize) * page // 2


def snapshot_bytes(conf, itemsize=2):
    return 0


def cache_rows(conf, position):
    """Rows a sequence holds with `position` positions written and
    every finished window folded."""
    m = _dims(conf)
    return position - (m["W"] - m["W"] // m["c"]) * (position // m["W"])


def least_rows(conf, active, live_tokens):
    """The fewest cache rows `active` sequences of `live_tokens`
    positions in all can hold: each as if it stood at a window's
    start, one row a chunk, and the row the step writes."""
    return live_tokens // _dims(conf)["c"] + active


def folded_attn_bytes(conf, rows, itemsize=2):
    """Bytes the attention of one decode step reads: `rows` cache rows
    (the step's own count, summed over its sequences) of K and V in
    every layer."""
    return _dims(conf)["L"] * rows * row_bytes(conf, itemsize)


def fold_bytes(conf, page=16, itemsize=2):
    """Bytes ONE fold moves: a window's pages read and its summary
    pages written, every layer: (128 + 8) x 3 MiB = 428 MB."""
    m = _dims(conf)
    pages = m["W"] // page + m["W"] // m["c"] // page
    return pages * full_page_bytes(conf, page, itemsize)


def _token_params(conf):
    m = _dims(conf)
    return m["L"] * (4 * m["d"] * m["d"] + mlp_params(conf))


def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
    """Bytes one decode step must read AT LEAST: every layer's
    weights, the head of all `num_pred_heads`, one embedding row a
    token, and `least_rows` cache rows."""
    m = _dims(conf)
    weights = (m["L"] * layer_params(conf)
               + m["d"] * m["heads"] * m["V"] + m["d"]) * itemsize \
        + active * m["d"] * itemsize
    return weights + folded_attn_bytes(
        conf, least_rows(conf, active, live_tokens), itemsize)


def decode_flops(conf, active, live_tokens):
    m = _dims(conf)
    return (2 * active * (_token_params(conf) + m["d"] * m["heads"] * m["V"])
            + m["L"] * m["H"] * least_rows(conf, active, live_tokens)
            * 4 * m["hd"])


def prefill_flops(conf, suffix, prefix=0):
    """FLOPs a piece inside one window needs: the suffix's matmuls,
    scores and weighted values over suffix x (prefix ROWS + the causal
    half of the suffix) pairs, the head for ONE position. `prefix` is
    in cache ROWS (`cache_rows` of the position the piece begins at);
    a caller that has positions passes that."""
    m = _dims(conf)
    pairs = suffix * prefix + suffix * (suffix + 1) // 2
    return (2 * suffix * _token_params(conf)
            + m["L"] * m["H"] * pairs * 4 * m["hd"]
            + 2 * m["d"] * m["heads"] * m["V"])
