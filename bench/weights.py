"""Random weights from the seed, in the program's parameter layout.

The benchmark makes the weights, not the program: the engine is handed
them (``bench/run.py``), and the plain reference regenerates the same
values one layer at a time after the window (``bench/check.py``), so the
reference takes nothing the program made.

Every leaf is drawn from its own key, ``fold_in(root, leaf_id)`` below a
per-layer key ``fold_in(layer_root, l)``: the values of layer ``l`` do not
depend on how many layers are drawn together, and a vmap over layers
gives the same bits as drawing one layer alone (``tests/test_weights.py``).

Biases and norm parameters are drawn too, not left at 0 and 1, so the
comparison exercises QKV bias, the norms' scales and offsets, and the
GELU MLP's biases.

A spec with ``n_experts`` holds routed SwiGLU experts in place of the
MLP, in ``models/moe.py``'s layout: ``moe`` = {``router`` (D, E) float32,
``w_gate`` and ``w_up`` (E, D, F), ``w_down`` (E, F, D)}, with ``d_ff``
one expert's width.  Those leaves have ids of their own (15..18), so a
dense spec draws the same bits as before they existed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A PRNG key from any whole seed, also one over 32 bits."""
    import numpy as np
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer_weights(key, spec: dict) -> dict:
    """One layer's parameters, in ``TransformerLM``'s per-layer layout."""
    D, H, K, dh, F = (spec["d_model"], spec["n_heads"], spec["n_kv_heads"],
                      spec["d_head"], spec["d_ff"])
    n = lambda i, shape, scale: _normal(jax.random.fold_in(key, i), shape,
                                        scale, spec["dtype"])
    attn = {"wq": n(0, (D, H, dh), D ** -0.5),
            "wk": n(1, (D, K, dh), D ** -0.5),
            "wv": n(2, (D, K, dh), D ** -0.5),
            "wo": n(3, (H, dh, D), (H * dh) ** -0.5)}
    if spec["qkv_bias"]:
        attn["bq"] = n(4, (H, dh), 0.5)
        attn["bk"] = n(5, (K, dh), 0.5)
        attn["bv"] = n(6, (K, dh), 0.5)
    p = {"attn": attn,
         "ln1": 1.0 + n(7, (D,), 0.1),
         "ln2": 1.0 + n(8, (D,), 0.1)}
    if spec["norm"] == "layernorm":
        p["ln1_b"] = n(9, (D,), 0.1)
        p["ln2_b"] = n(10, (D,), 0.1)
    if spec.get("n_experts"):
        E = spec["n_experts"]
        p["moe"] = {"router": _normal(jax.random.fold_in(key, 15), (D, E),
                                      D ** -0.5, jnp.float32),
                    "w_gate": n(16, (E, D, F), D ** -0.5),
                    "w_up": n(17, (E, D, F), D ** -0.5),
                    "w_down": n(18, (E, F, D), F ** -0.5)}
    elif spec["mlp"] == "swiglu":
        p["mlp"] = {"w_gate": n(11, (D, F), D ** -0.5),
                    "w_up": n(12, (D, F), D ** -0.5),
                    "w_down": n(13, (F, D), F ** -0.5)}
    else:
        p["mlp"] = {"w_up": n(11, (D, F), D ** -0.5),
                    "b_up": n(12, (F,), 0.1),
                    "w_down": n(13, (F, D), F ** -0.5),
                    "b_down": n(14, (D,), 0.1)}
    return p


def outer_weights(key, spec: dict) -> dict:
    """Embedding, final norm and LM head (untied)."""
    D, V = spec["d_model"], spec["vocab_size"]
    n = lambda i, shape, scale: _normal(jax.random.fold_in(key, i), shape,
                                        scale, spec["dtype"])
    p = {"tok_embed": n(0, (V, D), 1.0),
         "lm_head": n(1, (D, V), D ** -0.5),
         "ln_f": 1.0 + n(2, (D,), 0.1)}
    if spec["norm"] == "layernorm":
        p["ln_f_b"] = n(3, (D,), 0.1)
    return p


def layer_key(root, l: int):
    return jax.random.fold_in(jax.random.fold_in(root, 1), l)


def outer_key(root):
    return jax.random.fold_in(root, 0)


def make_params(root, spec: dict) -> dict:
    """All parameters, layers stacked on a leading axis: one jitted call
    on the default device (weights are never built on the host)."""
    L = spec["n_layers"]

    @jax.jit
    def build(root):
        keys = jax.vmap(lambda l: layer_key(root, l))(jnp.arange(L))
        params = {"layers": jax.vmap(lambda kk: layer_weights(kk, spec))(
            keys)}
        params.update(outer_weights(outer_key(root), spec))
        return params

    return build(root)
