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
