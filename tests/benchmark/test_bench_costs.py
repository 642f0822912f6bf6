"""Peaks table and the FLOP and byte functions, against numbers worked
by hand here."""

import json

import pytest

from benchmark.lib import costs, peaks


def conf(name):
    with open(f"benchmark/configs/{name}.json") as f:
        return json.load(f)


def test_v5e_peaks_and_their_source():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "v5e" in p["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "", "TPU v5"])
def test_an_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks(kind)


def test_mistral_16_layers_is_7_5_gb():
    c = conf("mistral7b")
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2          # wq wo, wk wv
    mlp = 3 * 4096 * 14336
    layer = attn + mlp + 2 * 4096
    total = 16 * layer + 2 * 32768 * 4096 + 4096
    assert costs.param_count(c) == total == 3_758_231_552
    assert costs.weight_bytes(c) == 2 * total            # 7.52 GB
    assert 7.4e9 < costs.weight_bytes(c) < 7.6e9


def test_mixtral_3_layers_all_experts():
    c = conf("mixtral8x7b")
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2
    layer = attn + 8 * 3 * 4096 * 14336 + 4096 * 8 + 2 * 4096
    total = 3 * layer + 2 * 32000 * 4096 + 4096
    assert costs.param_count(c) == total
    # bf16 everywhere but the float32 router
    assert costs.weight_bytes(c) == 2 * total + 3 * 4096 * 8 * 2
    assert 9.2e9 < costs.weight_bytes(c) < 9.3e9
    assert 1.44e9 < layer < 1.46e9                       # "1.45 B a layer"


@pytest.mark.parametrize("name,per_token,per_page", [
    ("mistral7b", 65536, 2 ** 20), ("mixtral8x7b", 12288, 196608),
])
def test_kv_bytes(name, per_token, per_page):
    c = conf(name)
    assert costs.kv_bytes_per_token(c) == per_token   # 2*L*8*128*2
    assert costs.page_bytes_all_layers(c) == per_page


def test_prefill_flops_by_hand_tiny():
    c = {"hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16,
         "num_hidden_layers": 2, "vocab_size": 10}
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8          # hd = 4
    act = attn + 3 * 8 * 16
    s, p = 3, 5
    pairs = s * p + s * (s + 1) // 2           # 15 + 6
    want = 2 * s * 2 * act + 2 * 2 * pairs * 4 * 4 + 2 * 8 * 10
    assert costs.prefill_flops(c, s, p) == want


def test_prefill_flops_mistral_cold_2288():
    c = conf("mistral7b")
    f = costs.prefill_flops(c, 2288)
    matmul = 2 * 2288 * 16 * (41_943_040 + 176_160_768)
    assert f > matmul and (f - matmul) / f < 0.1
    assert 16e12 < f < 17e12


def test_sparse_prefill_needs_only_the_chosen_experts():
    c = conf("mixtral8x7b")
    dense_waste = dict(c, num_experts_per_tok=8)
    assert costs.prefill_flops(dense_waste, 1024) \
        > 3.5 * costs.prefill_flops(c, 1024)


@pytest.mark.parametrize("tokens,want", [
    (0, 0.0), (1, 2.0), (16, 8 * (1 - 0.75 ** 16)),
])
def test_expected_experts(tokens, want):
    assert costs.expected_experts_touched(conf("mixtral8x7b"), tokens) \
        == pytest.approx(want)
    assert costs.expected_experts_touched(conf("mistral7b"), tokens) == 1.0


def test_decode_bytes_mistral_by_hand():
    c = conf("mistral7b")
    live = 16 * 1800
    layers = 16 * (41_943_040 + 176_160_768 + 2 * 4096) * 2
    head = (32768 * 4096 + 4096) * 2
    want = layers + head + 16 * 4096 * 2 + live * 65536
    assert costs.decode_bytes(c, 16, live) == pytest.approx(want)
    # 7.2 GB of weights at 819 GB/s is about 9 ms
    assert 8.5e-3 < (layers + head) / 819e9 < 9.5e-3


def test_decode_is_bound_by_bytes_not_flops_at_16_slots():
    for name in ("mistral7b", "mixtral8x7b"):
        c = conf(name)
        t_bytes = costs.decode_bytes(c, 16, 16 * 1800) / 819e9
        t_flops = costs.decode_flops(c, 16, 16 * 1800) / 197e12
        assert t_bytes > 5 * t_flops
