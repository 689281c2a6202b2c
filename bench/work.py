"""Operations and bytes the served work needs, from shapes and lengths.

What is counted is what the algorithm needs, never what an implementation
happens to do: attention reads each live K and V token once per KV head
and touches no dead page, whatever kernel runs it.  ``spec`` is the
``"model"`` block of a configuration file.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def matmul_params(spec: dict) -> int:
    """Weights one token multiplies per layer (projections and MLP)."""
    D, H, K, dh, F = (spec["d_model"], spec["n_heads"], spec["n_kv_heads"],
                      spec["d_head"], spec["d_ff"])
    attn = D * H * dh + 2 * D * K * dh + H * dh * D
    mlp = (3 if spec["mlp"] == "swiglu" else 2) * D * F
    return attn + mlp


def attn_flops(spec: dict, ctx) -> np.ndarray:
    """QKᵀ and PV for one query at context ``ctx``, over all layers."""
    return 4.0 * np.asarray(ctx, float) * spec["n_heads"] * spec["d_head"] \
        * spec["n_layers"]


def decode_attention(spec: dict, ctx: Iterable[int]) -> Tuple[float, float]:
    """(flops, bytes) of decode attention for one query per entry of
    ``ctx`` (its context length, the new token included), all layers:
    K and V of the live tokens read once per KV head, q read and the
    output written once per query head."""
    c = np.asarray(list(ctx), float)
    b = BYTES[spec["dtype"]]
    L, H, K, dh = (spec["n_layers"], spec["n_heads"], spec["n_kv_heads"],
                   spec["d_head"])
    flops = float(attn_flops(spec, c).sum())
    byts = float(L * (2 * c * K * dh * b + 2 * H * dh * b).sum())
    return flops, byts


def model_flops(spec: dict, decode_ctx: Iterable[int],
                prompts: Iterable[int], chunk: int) -> float:
    """Model FLOPs of the served tokens: 2 per weight a token multiplies,
    the attention its context needs, and the LM head for each row of
    logits computed (every decode token, and the last token of each
    prefill chunk).  A prompt of length n is n tokens at contexts 1..n."""
    L, D, V = spec["n_layers"], spec["d_model"], spec["vocab_size"]
    per_tok = 2.0 * L * matmul_params(spec)
    head = 2.0 * D * V
    dc = np.asarray(list(decode_ctx), float)
    total = dc.size * (per_tok + head) + attn_flops(spec, dc).sum()
    for n in prompts:
        ctx = np.arange(1, n + 1)
        total += n * per_tok + attn_flops(spec, ctx).sum() \
            + head * -(-n // chunk)
    return float(total)
