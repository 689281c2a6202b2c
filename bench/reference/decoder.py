"""Plain float32 forward of the pre-norm decoder both configurations use.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, no cache, no batching of requests, causal attention over the
whole sequence.  It imports nothing of the program.  Weights come from
``bench/weights.py`` one layer at a time and are upcast from the bfloat16
they are served in, so a run of 48 or 20 layers fits beside nothing else
on one chip.

What it follows, per configuration file (``"model"``):

- glm4-9b (hf THUDM/glm-4-9b): RMSNorm, QKV bias, grouped KV heads, SwiGLU,
  rotary embedding on the first half of each head's dimensions with
  adjacent pairs rotated together, as GLM's ``apply_rotary_pos_emb`` does.
- musicgen-large (arXiv 2306.05284): LayerNorm with bias, exact (erf) GELU
  MLP with biases, 32 heads with their own K and V.

Two optional parts of the ``"model"`` block, for configurations that
state them:

- Routed experts (``n_experts``, ``experts_per_token``): the MLP is a set
  of SwiGLU experts of width ``d_ff``; a float32 router's softmax over the
  top-k logits weights the k experts a token is routed to (the same as
  renormalising the top k of the full softmax).  The experts are looped
  over, each computed for every token and weighted by its gate (0 for a
  token not routed to it), so no (T, E, F) tensor is held.
- Window layers (``layer_kinds`` of ``bench/work.py``): on a layer of kind
  ``"window"`` key j is visible to query i iff i - sliding_window < j <= i.

Departures from the published models, all shared with the program being
checked: one token stream (MusicGen interleaves four EnCodec codebooks and
adds T5 text conditioning by cross-attention; neither is modelled), and
MusicGen's sinusoidal positions are replaced by the same rotary embedding
GLM uses, over the whole head.  The program rotates its rotary pairs as
halves (dims i and i + r/2) where GLM pairs neighbours (2i, 2i+1); that is
the same function under a fixed permutation of the rotary dimensions of
wq, wk, bq and bk, which ``_to_reference_layout`` applies.  The program's
MLP uses the tanh form of GELU; this uses the exact one.

``quant="int8"`` and ``quant="fp8"`` are the reference controls of
``bench/check.py``: every projection runs on int8 (W8A8) or float8 e4m3
operands (weights scaled per output column, activations per token,
symmetric), a step below the bfloat16 the configurations state; so do the
experts' gate, up and down projections.  The router stays float32 in both,
as the program keeps it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
# keys of the "model" block this reference reads that are no field of the
# program's ModelConfig (bench/run.py's ``model_config`` refuses others)
SPEC_KEYS = ()
QMAX = {"int8": 127.0, "fp8": 448.0}       # largest value of each format


def _quantize(quant, x, axis):
    """Symmetric scaling to the format's range, rounded there; returns the
    rounded values (as float32) and the scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-12) / QMAX[quant]
    if quant == "fp8":
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32), s
    return jnp.round(x / s), s


def _mm(quant, x, w, eq):
    """``einsum(eq, x, w)`` in float32, or on int8 / fp8 operands (the
    controls): x scaled per row over its last axis, w per output column
    over its first."""
    if quant is None:
        return jnp.einsum(eq, x, w)
    xq, sx = _quantize(quant, x, -1)
    wq, sw = _quantize(quant, w, 0)
    return jnp.einsum(eq, xq, wq) \
        * sx.reshape(sx.shape[:-1] + (1,) * (w.ndim - 1)) * sw[0]


def _norm(spec, x, g, b):
    if spec["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + spec["norm_eps"]) * g + b
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + spec["norm_eps"]) * g


def _rot_dims(spec) -> int:
    r = int(spec["d_head"] * spec["rope_fraction"])
    return r - r % 2


def _rope(spec, x, pos):
    """Rotate adjacent pairs (2i, 2i+1) of the first r dims by pos·θ_i."""
    r = _rot_dims(spec)
    inv = 1.0 / (spec["rope_theta"] ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = pos[:, None].astype(F32) * inv[None, :]           # (T, r/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xr = x[..., :r].reshape(x.shape[:-1] + (r // 2, 2))
    a, b = xr[..., 0], xr[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([out.reshape(x.shape[:-1] + (r,)), x[..., r:]], -1)


def _to_reference_layout(spec, w, axis=-1):
    """Program rotary layout (i, i + r/2 paired) -> adjacent pairs."""
    r = _rot_dims(spec)
    half = jnp.arange(r // 2)
    src = jnp.concatenate([jnp.stack([half, half + r // 2], -1).reshape(-1),
                           jnp.arange(r, spec["d_head"])])
    return jnp.take(w, src, axis=axis)


def _mask(T: int, window: int = 0):
    """(T, T) True where query i sees key j: j <= i, and i - window < j
    where ``window`` is set."""
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    m = j <= i
    return m & (j > i - window) if window else m


def _swiglu(quant, h, wg, wu, wd):
    u = jax.nn.silu(_mm(quant, h, wg, "td,df->tf")) \
        * _mm(quant, h, wu, "td,df->tf")
    return _mm(quant, u, wd, "tf,fd->td")


def _experts(spec, m, h, quant):
    """Routed SwiGLU experts: softmax over the top-k router logits, each
    expert computed in turn for every token and weighted by its gate."""
    logits = jnp.einsum("td,de->te", h, m["router"])
    top, idx = jax.lax.top_k(logits, spec["experts_per_token"])
    w = jax.nn.softmax(top, -1)
    gates = jnp.sum(jax.nn.one_hot(idx, spec["n_experts"], dtype=F32)
                    * w[..., None], axis=1)                         # (T, E)

    def one(y, e):
        wg, wu, wd, g = e
        return y + g[:, None] * _swiglu(quant, h, wg, wu, wd), None
    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (m["w_gate"], m["w_up"], m["w_down"], gates.T))
    return y


def layer(spec, p, x, quant=None, kind="full"):
    """One decoder layer over a whole sequence ``x`` (T, D), float32;
    ``kind`` is the layer's kind of attention, "full" or "window"."""
    T = x.shape[0]
    H, K, dh = spec["n_heads"], spec["n_kv_heads"], spec["d_head"]
    a = jax.tree.map(lambda t: t.astype(F32), p["attn"])
    ln = lambda n, y: _norm(spec, y, p[n].astype(F32),
                            p.get(n + "_b", jnp.zeros(())).astype(F32))
    h = ln("ln1", x)
    q = _mm(quant, h, a["wq"], "td,dhk->thk")
    k = _mm(quant, h, a["wk"], "td,dhk->thk")
    v = _mm(quant, h, a["wv"], "td,dhk->thk")
    if spec["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    pos = jnp.arange(T)
    q = _rope(spec, _to_reference_layout(spec, q), pos)
    k = _rope(spec, _to_reference_layout(spec, k), pos)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("thk,shk->hts", q, k) / math.sqrt(dh)
    window = spec["sliding_window"] if kind == "window" else 0
    s = jnp.where(_mask(T, window)[None], s, -jnp.inf)
    o = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, -1), v)
    x = x + _mm(quant, o.reshape(T, H * dh), a["wo"].reshape(H * dh, -1),
                "tf,fd->td")
    h = ln("ln2", x)
    if "moe" in p:
        m = jax.tree.map(lambda t: t.astype(F32), p["moe"])
        return x + _experts(spec, m, h, quant)
    m = jax.tree.map(lambda t: t.astype(F32), p["mlp"])
    if spec["mlp"] == "swiglu":
        return x + _swiglu(quant, h, m["w_gate"], m["w_up"], m["w_down"])
    u = jax.nn.gelu(_mm(quant, h, m["w_up"], "td,df->tf") + m["b_up"],
                    approximate=False)
    return x + _mm(quant, u, m["w_down"], "tf,fd->td") + m["b_down"]


def embed(p, tokens):
    return jnp.take(p["tok_embed"], tokens, axis=0).astype(F32)


def logits(spec, p, x, quant=None):
    """Final norm and LM head: (T, D) -> (T, V) float32."""
    h = _norm(spec, x, p["ln_f"].astype(F32),
              p.get("ln_f_b", jnp.zeros(())).astype(F32))
    return _mm(quant, h, p["lm_head"].astype(F32), "td,dv->tv")
