"""Operations and bytes the served work needs, from shapes and lengths.

What is counted is what the algorithm needs, never what an implementation
happens to do: attention reads each live K and V token once per KV head
and touches no dead page, whatever kernel runs it; a window layer's query
sees at most ``sliding_window`` keys; a routed expert layer multiplies
only the experts a token is routed to.  ``spec`` is the ``"model"`` block
of a configuration file.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple

import numpy as np

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_kinds(spec: dict) -> List[str]:
    """Each layer's kind of attention, ``"full"`` or ``"window"``: the
    spec's ``layer_types``, or without it every layer ``"window"`` where
    ``sliding_window`` is set and ``"full"`` where it is not."""
    kinds = spec.get("layer_types")
    if kinds is None:
        return ["window" if spec.get("sliding_window") else "full"] \
            * spec["n_layers"]
    return list(kinds)


def matmul_params(spec: dict) -> int:
    """Weights one token multiplies per layer: the projections, and the
    MLP or, with ``n_experts``, its ``experts_per_token`` SwiGLU experts
    and the router."""
    D, H, K, dh, F = (spec["d_model"], spec["n_heads"], spec["n_kv_heads"],
                      spec["d_head"], spec["d_ff"])
    attn = D * H * dh + 2 * D * K * dh + H * dh * D
    if spec.get("n_experts"):
        return attn + spec["experts_per_token"] * 3 * D * F \
            + D * spec["n_experts"]
    mlp = (3 if spec["mlp"] == "swiglu" else 2) * D * F
    return attn + mlp


def _keys(spec: dict, ctx) -> np.ndarray:
    """Σ over layers of the keys a query at context ``ctx`` attends to:
    ``ctx`` on a full layer, ``min(ctx, sliding_window)`` on a window
    layer."""
    c = np.asarray(ctx, float)
    kinds = layer_kinds(spec)
    n_win = kinds.count("window")
    return c * (len(kinds) - n_win) \
        + np.minimum(c, spec.get("sliding_window", 0)) * n_win


def attn_flops(spec: dict, ctx) -> np.ndarray:
    """QKᵀ and PV for one query at context ``ctx``, over all layers."""
    return 4.0 * _keys(spec, ctx) * spec["n_heads"] * spec["d_head"]


def decode_attention(spec: dict, ctx: Iterable[int]) -> Tuple[float, float]:
    """(flops, bytes) of decode attention for one query per entry of
    ``ctx`` (its context length, the new token included), all layers:
    K and V of the live tokens in each layer's reach read once per KV
    head, q read and the output written once per query head."""
    c = np.asarray(list(ctx), float)
    b = BYTES[spec["dtype"]]
    L, H, K, dh = (spec["n_layers"], spec["n_heads"], spec["n_kv_heads"],
                   spec["d_head"])
    flops = float(attn_flops(spec, c).sum())
    byts = float((2 * _keys(spec, c) * K * dh * b + L * 2 * H * dh * b).sum())
    return flops, byts


def routed_experts(spec: dict, tokens) -> Tuple[float, float]:
    """(flops, bytes) a routed expert layer needs for one step, from the
    tokens each expert received (``tokens``: counts of any shape, one per
    expert and layer): 6·D·F FLOPs for each routed token (gate, up and
    down projections) and the 3·D·F weights of each expert that received
    any token, read once."""
    t = np.asarray(tokens, float)
    D, F, b = spec["d_model"], spec["d_ff"], BYTES[spec["dtype"]]
    return float(6.0 * D * F * t.sum()), \
        float(3.0 * D * F * b * np.count_nonzero(t))


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
