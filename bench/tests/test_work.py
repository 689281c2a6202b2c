"""``bench/work.py`` against arithmetic done by hand for both
configurations."""
import json
from pathlib import Path

import pytest

from bench import work

CONF = Path(__file__).resolve().parents[1] / "configs"


def spec(name):
    return json.loads((CONF / f"{name}.json").read_text())["model"]


def test_matmul_params_by_hand():
    # musicgen: 4 x 2048^2 attention + 2 x 2048 x 8192 GELU MLP per layer
    assert work.matmul_params(spec("musicgen-large")) == 50_331_648
    # glm4: q,o 4096 x 4096, k,v 4096 x 256, SwiGLU 3 x 4096 x 13696
    g = spec("glm4-9b")
    assert work.matmul_params(g) == 203_948_032
    # 20 layers + embedding and LM head = the 5.32 B parameters served
    assert 20 * 203_948_032 + 2 * 4096 * 151_552 == 5_320_474_624


@pytest.mark.parametrize("name,flops,byts", [
    # 4 x 100 x 32 heads x 64 x 48 layers;
    # 48 x (K,V: 2 x 100 x 32 x 64 x 2 B + q,out: 2 x 32 x 64 x 2 B)
    ("musicgen-large", 39_321_600, 39_714_816),
    # 4 x 100 x 32 x 128 x 20; 20 x (2 x 100 x 2 x 128 x 2 + 2 x 32 x 128 x 2)
    ("glm4-9b", 32_768_000, 2_375_680),
])
def test_decode_attention_by_hand(name, flops, byts):
    assert work.decode_attention(spec(name), [100]) == (flops, byts)


def test_decode_attention_adds_over_tokens():
    s = spec("glm4-9b")
    f1, b1 = work.decode_attention(s, [10])
    f2, b2 = work.decode_attention(s, [30])
    assert work.decode_attention(s, [10, 30]) == (f1 + f2, b1 + b2)


def test_model_flops_by_hand():
    s = spec("musicgen-large")
    per_tok = 2 * 48 * 50_331_648
    head = 2 * 2048 * 2048
    # one decode token at context 5
    assert work.model_flops(s, [5], [], 64) == \
        per_tok + head + 4 * 5 * 32 * 64 * 48
    # a 3-token prompt: contexts 1, 2, 3 and one row of logits
    assert work.model_flops(s, [], [3], 64) == \
        3 * per_tok + 4 * 6 * 32 * 64 * 48 + head
    # 130 tokens in 64-token chunks: three rows of logits
    assert work.model_flops(s, [], [130], 64) - \
        work.model_flops(s, [], [130], 200) == 2 * head


# Mellum2-12B-A2.5B (hf JetBrains/Mellum2-12B-A2.5B-Instruct) at 8 of its
# 28 layers: two periods of (window, window, window, full)
MELLUM = {"n_layers": 8, "d_model": 2304, "n_heads": 32, "n_kv_heads": 4,
          "d_head": 128, "d_ff": 896, "vocab_size": 98304, "norm": "rmsnorm",
          "norm_eps": 1e-06, "mlp": "swiglu", "qkv_bias": False,
          "rope_theta": 500000.0, "rope_fraction": 1.0, "dtype": "bfloat16",
          "n_experts": 64, "experts_per_token": 8, "sliding_window": 1024,
          "layer_types": ["window", "window", "window", "full"] * 2}


def test_routed_matmul_params_by_hand():
    # q,o 2304 x 4096; k,v 2304 x 512
    attn = 2 * 2304 * 4096 + 2 * 2304 * 512
    assert attn == 21_233_664
    # 8 of 64 experts, each 3 x 2304 x 896; the router 2304 x 64
    assert work.matmul_params(MELLUM) == attn + 8 * 3 * 2304 * 896 \
        + 2304 * 64 == 70_926_336


@pytest.mark.parametrize("ctx,keys", [
    # inside the window every layer sees the whole context
    (500, 8 * 500),
    # past it the six window layers see 1024 keys, the two full ones all
    (2000, 2 * 2000 + 6 * 1024),
])
def test_windowed_attention_by_hand(ctx, keys):
    # 4 x keys x 32 heads x 128; K,V: 2 x keys x 4 x 128 x 2 B, plus
    # q and the output of 32 heads x 128 x 2 B on each of the 8 layers
    assert work.decode_attention(MELLUM, [ctx]) == (
        4 * keys * 32 * 128, 2 * keys * 4 * 128 * 2 + 8 * 2 * 32 * 128 * 2)


def test_window_without_layer_types_windows_every_layer():
    s = dict(MELLUM, n_layers=4)
    del s["layer_types"]
    assert work.layer_kinds(s) == ["window"] * 4
    assert work.attn_flops(s, 3000) == 4 * 4 * 1024 * 32 * 128
    assert work.layer_kinds(spec("glm4-9b")) == ["full"] * 20


def test_routed_model_flops_by_hand():
    per_tok = 2 * 8 * 70_926_336
    head = 2 * 2304 * 98304
    assert work.model_flops(MELLUM, [2000], [], 256) == \
        per_tok + head + 4 * (2 * 2000 + 6 * 1024) * 32 * 128


def test_routed_experts_by_hand():
    # two layers of three experts: 6 routed tokens, 3 experts with any
    tokens = [[3, 0, 1], [0, 0, 2]]
    assert work.routed_experts(MELLUM, tokens) == (
        6 * 2304 * 896 * 6, 3 * 2304 * 896 * 2 * 3)
    assert work.routed_experts(MELLUM, [0, 0]) == (0.0, 0.0)
