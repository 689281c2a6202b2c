"""Common neural layers: norms, RoPE, GQA attention (with expanded-KV TP
layout and head padding), SwiGLU/GELU MLPs, embeddings.

All functions are pure; parameters are plain nested dicts of jnp arrays.
Sharding is expressed only through a :class:`~repro.models.partitioning.Partitioner`
so the same code runs unsharded (smoke tests) or on a production mesh.

Head layout for tensor parallelism (DESIGN.md §4):
  Hp  — query heads zero-padded to a multiple of the TP degree,
  KvE — KV heads expanded (zero-pad + nearest-repeat) to ``max(pad(K), tp)``;
        the repeat happens on *activations* so GQA gradients stay exact.
The K/V cache stores the expanded layout: its head axis sharding is identical
to the query-head sharding — the paper's co-location invariant.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.partitioning import Partitioner
from repro.models.quantization import wt

# ---------------------------------------------------------------------------
# Derived head dims
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeadDims:
    H: int      # logical query heads
    K: int      # logical kv heads
    Hp: int     # padded query heads
    Kp: int     # zero-padded kv heads (before repeat)
    rep: int    # activation repeat factor
    KvE: int    # expanded kv heads stored in the cache = Kp * rep
    dh: int

    @property
    def groups(self) -> int:
        return self.Hp // self.KvE


def head_dims(cfg: ModelConfig, tp: int = 1) -> HeadDims:
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if H == 0:
        return HeadDims(0, 0, 0, 0, 1, 0, dh)
    Hp = -(-H // tp) * tp
    if K >= tp:
        Kp = -(-K // tp) * tp
        rep = 1
    else:
        # tp > K: repeat each kv head so every chip holds exactly the KV
        # group(s) its local Q heads attend to.
        Kp = K
        rep = tp // K if tp % K == 0 else tp  # tp%K!=0 never occurs for our archs
    KvE = Kp * rep
    assert Hp % KvE == 0, f"GQA layout mismatch H={H} K={K} tp={tp}"
    return HeadDims(H, K, Hp, Kp, rep, KvE, dh)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in: int, shape: Tuple[int, ...], dtype) -> jnp.ndarray:
    return _normal(key, shape, 1.0 / math.sqrt(d_in), dtype)


def zero_pad_heads(w: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    """Zero-pad a head axis (padded heads never influence outputs: the
    corresponding o-proj rows are zero as well)."""
    pad = to - w.shape[axis]
    if pad == 0:
        return w
    widths = [(0, 0)] * w.ndim
    widths[axis] = (0, pad)
    return jnp.pad(w, widths)


# ---------------------------------------------------------------------------
# Differentiable optimization barrier (layer-slice pinning under scan)
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _diff_opt_barrier(flat):
    return jax.lax.optimization_barrier(flat)


def _dob_fwd(flat):
    return jax.lax.optimization_barrier(flat), None


def _dob_bwd(_, g):
    # pin the cotangents too — the backward scan has the same
    # gather-of-slice hoisting exposure on the gradients; float0 /
    # symbolic-zero leaves (int inputs) pass through untouched.
    out = [t if t is None or getattr(t, "dtype", None) == jax.dtypes.float0
           else jax.lax.optimization_barrier(t) for t in g]
    return (out,)


_diff_opt_barrier.defvjp(_dob_fwd, _dob_bwd)


def pin_layer_slice(xs):
    """``jax.lax.optimization_barrier`` over a pytree, usable under
    ``jax.grad``: ``optimization_barrier`` has no differentiation rule, so
    training steps that scan over barriered stacked layer params failed to
    trace.  Identity VJP with barriered cotangents keeps the FSDP
    no-hoist property (see TransformerLM._barrier) in both directions."""
    flat, td = jax.tree_util.tree_flatten(xs)
    return jax.tree_util.tree_unflatten(td, _diff_opt_barrier(flat))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg: ModelConfig, p: dict, name: str, x):
    if cfg.norm_type == "layernorm":
        return layer_norm(x, p[name], p[name + "_b"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


def init_norm(cfg: ModelConfig, d: int, dtype) -> dict:
    out = {"": jnp.ones((d,), dtype)}
    if cfg.norm_type == "layernorm":
        out["_b"] = jnp.zeros((d,), dtype)
    return out


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(dh_rot: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dh_rot, 2, dtype=jnp.float32) / dh_rot))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               fraction: float = 1.0) -> jnp.ndarray:
    """x: (B, S, n_heads, dh); positions: (B, S) int32. Rotates the first
    ``fraction`` of the head dim (GLM-4 rotates half)."""
    B, S, N, dh = x.shape
    dh_rot = int(dh * fraction)
    if dh_rot % 2:
        dh_rot -= 1
    freqs = rope_freqs(dh_rot, theta)                       # (dh_rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh_rot/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr = x[..., :dh_rot].astype(jnp.float32)
    x1, x2 = xr[..., : dh_rot // 2], xr[..., dh_rot // 2:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    out = jnp.concatenate([rotated.astype(x.dtype), x[..., dh_rot:]], axis=-1)
    return out


# ---------------------------------------------------------------------------
# Attention (GQA, causal / sliding window / cross, cache-aware)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, hd: HeadDims, *, cross: bool = False) -> dict:
    D, dtype = cfg.d_model, jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": zero_pad_heads(dense_init(ks[0], D, (D, hd.H, hd.dh), dtype), 1, hd.Hp),
        "wk": zero_pad_heads(dense_init(ks[1], D, (D, hd.K, hd.dh), dtype), 1, hd.Kp),
        "wv": zero_pad_heads(dense_init(ks[2], D, (D, hd.K, hd.dh), dtype), 1, hd.Kp),
        "wo": zero_pad_heads(dense_init(ks[3], hd.H * hd.dh, (hd.H, hd.dh, D), dtype), 0, hd.Hp),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hd.Hp, hd.dh), dtype)
        p["bk"] = jnp.zeros((hd.Kp, hd.dh), dtype)
        p["bv"] = jnp.zeros((hd.Kp, hd.dh), dtype)
    if cross:
        # gated cross-attention (llama-3.2-vision style)
        p["gate"] = jnp.zeros((), dtype)
    return p


def qkv_project(cfg: ModelConfig, p: dict, hd: HeadDims, x, kv_x,
                positions, kv_positions, part: Partitioner,
                rope: bool = True):
    """Returns q (B,S,Hp,dh) and expanded k, v (B,T,KvE,dh)."""
    q = jnp.einsum("bsd,dhk->bshk", x, wt(p, "wq", x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, wt(p, "wk", x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, wt(p, "wv", x.dtype))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, kv_positions, cfg.rope_theta, cfg.rope_fraction)
    if hd.rep > 1:  # expand on activations => exact GQA gradients
        k = jnp.repeat(k, hd.rep, axis=2)
        v = jnp.repeat(v, hd.rep, axis=2)
    q = part.constrain(q, ("batch", "seq", "heads", None))
    k = part.constrain(k, ("batch", "seq", "kv_heads", None))
    v = part.constrain(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def attention_scores(q, k, v, mask, part: Partitioner):
    """q: (B,S,Hp,dh), k/v: (B,T,KvE,dh), mask: broadcastable to (B,1,1,S,T)
    or None. Returns (B,S,Hp,dh). Softmax in f32."""
    B, S, Hp, dh = q.shape
    T, KvE = k.shape[1], k.shape[2]
    G = Hp // KvE
    qg = q.reshape(B, S, KvE, G, dh)
    scores = jnp.einsum("bsegd,bted->begst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("begst,bted->bsegd", probs.astype(v.dtype), v)
    out = out.reshape(B, S, Hp, dh)
    return part.constrain(out, ("batch", "seq", "heads", None))


def chunked_attention(q, k, v, q_positions, kv_positions, part: Partitioner,
                      *, causal: bool = True, window: int = 0,
                      chunk: int = 1024, kv_valid=None):
    """Flash-style attention in pure XLA: lax.scan over KV chunks with
    online-softmax running (m, l, acc) — peak memory O(S·chunk) instead of
    O(S²).  This is the memory-sane formulation every production system
    uses for long-sequence prefill/training; the Pallas kernel is its TPU
    twin (kernels/flash_attention.py).

    q: (B,S,Hp,dh); k/v: (B,T,KvE,dh); positions (B,S)/(B,T);
    kv_valid: optional scalar count of valid cache entries.
    Returns (B,S,Hp,dh) in q.dtype.
    """
    B, S, Hp, dh = q.shape
    T, KvE = k.shape[1], k.shape[2]
    G = Hp // KvE
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nk = T // chunk
    scale = 1.0 / math.sqrt(dh)
    qg = (q.astype(jnp.float32) * scale).reshape(B, S, KvE, G, dh)
    kc = k.reshape(B, nk, chunk, KvE, dh)
    vc = v.reshape(B, nk, chunk, KvE, dh)
    pc = kv_positions.reshape(B, nk, chunk)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs                                  # (B,chunk,KvE,dh)
        s = jnp.einsum("bsegd,bted->begst", qg, kb.astype(jnp.float32))
        pred = jnp.ones((B, S, chunk), jnp.bool_)
        if causal:
            pred = pb[:, None, :] <= q_positions[:, :, None]
            if window > 0:
                pred &= pb[:, None, :] > (q_positions[:, :, None] - window)
        if kv_valid is not None:
            pred &= (pb < kv_valid)[:, None, :]
        s = jnp.where(pred[:, None, None], s, -1e30)   # (B,1,1,S,chunk)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + \
            jnp.einsum("begst,bted->begsd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, KvE, G, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KvE, G, S), jnp.float32)
    a0 = jnp.zeros((B, KvE, G, S, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.moveaxis(pc, 1, 0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, S, Hp, dh).astype(q.dtype)
    return part.constrain(out, ("batch", "seq", "heads", None))


def causal_mask(q_positions, kv_positions, window: int = 0):
    """(B,1,1,S,T) boolean; True = attend. window=0 means full causal."""
    m = kv_positions[:, None, :] <= q_positions[:, :, None]
    if window > 0:
        m &= kv_positions[:, None, :] > (q_positions[:, :, None] - window)
    return m[:, None, None, :, :]


def _decode_lengths(cache_pos, B: int):
    """Valid-cache-length vector for the flash-decode kernel: the current
    token writes at ``cache_pos`` and attends positions <= its own, so the
    kernel's per-row length is ``pos + 1`` (scalar positions broadcast —
    lock-step batches share one depth)."""
    cp = jnp.asarray(cache_pos, jnp.int32)
    if cp.ndim == 0:
        cp = jnp.broadcast_to(cp, (B,))
    return cp + 1


def _head_rows_or_identity(head_rows, head_inv, n_rows: int):
    """Gather/scatter maps for the resident-slice kernel; identity (dense
    grid over all rows, no scatter) when no placement maps are threaded."""
    if head_rows is None:
        return jnp.arange(n_rows, dtype=jnp.int32), None
    return head_rows, head_inv


def _decode_kernel_ok(T: int) -> bool:
    """The flash-decode kernel streams the cache in ``bk``-sized blocks;
    a cache extent that does not tile (T > bk and T % bk != 0 — e.g. the
    1601-token VLM image stub) keeps the jnp path."""
    from repro.kernels.decode_attention import DEFAULT_BK
    return T % min(DEFAULT_BK, T) == 0


def _q8(t):
    """Per-(token, head) int8 quantization over dh: (values int8, scales
    f32) — one definition shared by the dense and paged int8 cache
    branches so their stored values cannot diverge."""
    sc = jnp.maximum(jnp.max(jnp.abs(t.astype(jnp.float32)),
                             axis=-1), 1e-8) / 127.0
    qq = jnp.clip(jnp.round(t.astype(jnp.float32) / sc[..., None]),
                  -127, 127).astype(jnp.int8)
    return qq, sc.astype(jnp.float32)


def _project_out(p: dict, out, part: Partitioner, *, gate=None):
    """Shared attention output tail: wo projection (plus the VLM
    cross-attention gate when given), constrained to the residual layout
    — one definition so the kernel and jnp branches cannot diverge."""
    out = jnp.einsum("bshk,hkd->bsd", out, wt(p, "wo", out.dtype))
    if gate is not None:
        out = out * jnp.tanh(gate).astype(out.dtype)
    return part.constrain(out, ("batch", "res_seq", "d_model"))


def self_attention_block(cfg: ModelConfig, p: dict, hd: HeadDims, x,
                         positions, part: Partitioner, *,
                         cache=None, cache_pos=None, window: int = 0,
                         use_kernel: bool = False, head_rows=None,
                         head_inv=None, page_map=None, write_valid=None):
    """Causal self-attention with optional KV cache.

    cache: dict {"k","v"[, "pos"]} of (B, cache_len, KvE, dh) buffers.
      - linear cache (cache_len == max_seq): new K/V written at ``cache_pos``;
      - ring cache (sliding window, cache_len == window, decode S=1): slot
        ``cache_pos % window``; "pos" (window,) holds absolute positions
        (init to a large negative so empty slots never pass the mask).
      - paged cache (``page_map`` is not None): cache buffers are pooled
        pages (n_pages, P, KvE, dh) shared by all slots; ``page_map``
        (B, np) int32 maps row b's logical page i to a physical page id
        (-1 = unmapped: writes there DROP, reads clamp to page 0 and are
        hidden by the causal mask).  ``write_valid`` (B, S) bool masks
        which of this call's tokens actually store K/V (chunked prefill
        tails) — attention itself is masked by positions as usual.
    cache_pos: absolute position of the first query token — a scalar int32,
      or a (B,) int32 vector for slot-level continuous batching (linear
      cache, S == 1 only): row b writes its new K/V at its own position
      ``cache_pos[b]`` and the causal mask is taken per row, so slots at
      different sequence depths decode in one batch.
    use_kernel: S == 1 linear-cache decode dispatches to the Pallas
      flash-decode kernel (``ops.decode_attention_resident_bshd``; the
      int8 cache uses the fused int8 variant) instead of the jnp path.
      ``head_rows``/``head_inv`` are that kernel's per-layer gather/
      scatter maps — the PHYSICAL q-head rows in slot-grouped placement
      order (``placement_bridge.head_row_maps``); None runs the identity
      (dense) grid.  Ring caches and windowed attention keep the jnp path
      (their validity set is not a prefix).
    Returns (out, new_cache).
    """
    B, S = x.shape[0], x.shape[1]
    q, k, v = qkv_project(cfg, p, hd, x, x, positions, positions, part)

    def attend(kk, vv, kv_pos, mask):
        """Chunked (flash-style) when the KV extent is long, else vanilla."""
        T = kk.shape[1]
        ch = 1024
        if S > 1 and T >= 2048 and T % ch == 0:
            return chunked_attention(q, kk, vv, positions, kv_pos, part,
                                     causal=True, window=window, chunk=ch)
        return attention_scores(q, kk, vv, mask, part)

    new_cache = None
    if cache is not None and page_map is not None:
        # ---- paged cache: pooled pages + per-row page table -----------
        n_pages, P = cache["k"].shape[0], cache["k"].shape[1]
        np_log = page_map.shape[1]
        Tmax = np_log * P
        pos32 = positions.astype(jnp.int32)                       # (B, S)
        lpage = jnp.clip(pos32 // P, 0, np_log - 1)
        phys = jnp.take_along_axis(page_map, lpage, axis=1)       # (B, S)
        # unmapped/invalid writes go to a POSITIVE out-of-bounds index so
        # mode="drop" drops them (-1 would wrap to the last page slot)
        oob = jnp.int32(n_pages * P)
        w_idx = jnp.where(phys >= 0, phys * P + pos32 % P, oob)
        if write_valid is not None:
            w_idx = jnp.where(write_valid, w_idx, oob)
        w_flat = w_idx.reshape(B * S)
        gmap = jnp.maximum(page_map, 0)                           # (B, np)
        g_idx = (gmap[:, :, None] * P
                 + jnp.arange(P, dtype=jnp.int32)[None, None, :]
                 ).reshape(B, Tmax)
        # pages sit in the table in LOGICAL order, so the gathered view
        # is position-ordered and the standard causal mask applies
        kv_pos = jnp.broadcast_to(
            jnp.arange(Tmax, dtype=jnp.int32)[None, :], (B, Tmax))

        def scatter(buf, new):
            with jax.named_scope("kv_write"):
                flat = buf.reshape((n_pages * P,) + buf.shape[2:])
                flat = flat.at[w_flat].set(
                    new.reshape((B * S,) + new.shape[2:]), mode="drop")
                return flat.reshape(buf.shape)

        def gather(buf):
            flat = buf.reshape((n_pages * P,) + buf.shape[2:])
            return jnp.take(flat, g_idx, axis=0)          # (B, Tmax, ...)

        rows_m = inv = None
        if use_kernel and S == 1 and cache_pos is not None:
            rows_m, inv = _head_rows_or_identity(head_rows, head_inv,
                                                 q.shape[2])
        if "k_sc" in cache:
            kq, ksc = _q8(k)
            vq, vsc = _q8(v)
            ck, cv = scatter(cache["k"], kq), scatter(cache["v"], vq)
            cks = scatter(cache["k_sc"], ksc)
            cvs = scatter(cache["v_sc"], vsc)
            ck = part.constrain(ck, (None, None, "kv_heads", None))
            cv = part.constrain(cv, (None, None, "kv_heads", None))
            new_cache = dict(cache, k=ck, v=cv, k_sc=cks, v_sc=cvs)
            if rows_m is not None:
                from repro.kernels import ops
                out = ops.decode_attention_int8_paged_bshd(
                    q, ck, cks, cv, cvs, _decode_lengths(cache_pos, B),
                    gmap, rows_m, inv_rows=inv)
                return _project_out(p, out, part), new_cache
            kd = (gather(ck).astype(jnp.float32)
                  * gather(cks)[..., None]).astype(x.dtype)
            vd = (gather(cv).astype(jnp.float32)
                  * gather(cvs)[..., None]).astype(x.dtype)
            mask = causal_mask(positions, kv_pos, 0)
            out = attend(kd, vd, kv_pos, mask)
            return _project_out(p, out, part), new_cache
        ck, cv = scatter(cache["k"], k), scatter(cache["v"], v)
        ck = part.constrain(ck, (None, None, "kv_heads", None))
        cv = part.constrain(cv, (None, None, "kv_heads", None))
        new_cache = dict(cache, k=ck, v=cv)
        if rows_m is not None:
            from repro.kernels import ops
            out = ops.decode_attention_paged_bshd(
                q, ck, cv, _decode_lengths(cache_pos, B), gmap, rows_m,
                inv_rows=inv)
            return _project_out(p, out, part), new_cache
        mask = causal_mask(positions, kv_pos, 0)
        out = attend(gather(ck), gather(cv), kv_pos, mask)
        return _project_out(p, out, part), new_cache
    if cache is not None:
        cache_len = cache["k"].shape[1]
        ring = window > 0 and cache_len == window
        if ring and S > 1:
            # Sliding-window prefill: attend on the full in-flight K/V (the
            # window mask hides everything older), then fold the last
            # ``window`` tokens into the ring buffer (slot t%window <- pos t).
            mask = causal_mask(positions, positions, window)
            out = attend(k, v, positions, mask)
            out = _project_out(p, out, part)
            if S >= window:
                tail_k, tail_v = k[:, -window:], v[:, -window:]
                tail_pos = positions[0, -window:].astype(jnp.int32)
            else:
                pad = window - S
                tail_k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                tail_v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                tail_pos = jnp.concatenate(
                    [positions[0].astype(jnp.int32),
                     jnp.full((pad,), -2**30, jnp.int32)])
            shift = tail_pos[0] % window
            ck = jnp.roll(tail_k, shift, axis=1)
            cv = jnp.roll(tail_v, shift, axis=1)
            slot_pos = jnp.roll(tail_pos, shift)
            ck = part.constrain(ck, ("batch", "cache_seq", "kv_heads", None))
            cv = part.constrain(cv, ("batch", "cache_seq", "kv_heads", None))
            new_cache = dict(cache, k=ck, v=cv, pos=slot_pos)
            return out, new_cache
        if ring:
            idx = jnp.asarray(cache_pos, jnp.int32) % window
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, idx, 0, 0))
            slot_pos = jax.lax.dynamic_update_slice(
                cache["pos"], jnp.reshape(cache_pos, (1,)).astype(jnp.int32), (idx,))
            kv_pos = jnp.broadcast_to(slot_pos[None, :], (B, window))
        elif "k_sc" in cache:
            # int8 KV cache: quantize the new tokens per (token, head) over
            # dh, update values+scales, dequantize for the attention read
            kq, ksc = _q8(k)
            vq, vsc = _q8(v)
            if getattr(cache_pos, "ndim", 0) == 1:
                # per-slot write (continuous batching, S == 1): row b's
                # quantized K/V and scales land at its own position, same
                # drop-at-the-edge rule as the fp per-slot branch below
                rows_b = jnp.arange(B)
                cp = jnp.asarray(cache_pos, jnp.int32)
                ck = cache["k"].at[rows_b, cp].set(kq[:, 0], mode="drop")
                cv = cache["v"].at[rows_b, cp].set(vq[:, 0], mode="drop")
                cks = cache["k_sc"].at[rows_b, cp].set(ksc[:, 0],
                                                       mode="drop")
                cvs = cache["v_sc"].at[rows_b, cp].set(vsc[:, 0],
                                                       mode="drop")
            else:
                ck = jax.lax.dynamic_update_slice(cache["k"], kq,
                                                  (0, cache_pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"], vq,
                                                  (0, cache_pos, 0, 0))
                cks = jax.lax.dynamic_update_slice(cache["k_sc"], ksc,
                                                   (0, cache_pos, 0))
                cvs = jax.lax.dynamic_update_slice(cache["v_sc"], vsc,
                                                   (0, cache_pos, 0))
            ck = part.constrain(ck, ("batch", "cache_seq", "kv_heads", None))
            cv = part.constrain(cv, ("batch", "cache_seq", "kv_heads", None))
            new_cache = dict(cache, k=ck, v=cv, k_sc=cks, v_sc=cvs)
            if use_kernel and S == 1 and window == 0 \
                    and _decode_kernel_ok(cache_len):
                from repro.kernels import ops
                rows, inv = _head_rows_or_identity(head_rows, head_inv,
                                                   q.shape[2])
                out = ops.decode_attention_int8_resident_bshd(
                    q, ck, cks, cv, cvs, _decode_lengths(cache_pos, B),
                    rows, inv_rows=inv)
                return _project_out(p, out, part), new_cache
            kv_pos = jnp.broadcast_to(
                jnp.arange(cache_len, dtype=jnp.int32)[None, :], (B, cache_len))
            kd = (ck.astype(jnp.float32) * cks[..., None]).astype(x.dtype)
            vd = (cv.astype(jnp.float32) * cvs[..., None]).astype(x.dtype)
            mask = causal_mask(positions, kv_pos, window)
            out = attend(kd, vd, kv_pos, mask)
            return _project_out(p, out, part), new_cache
        elif getattr(cache_pos, "ndim", 0) == 1:
            # per-slot linear cache write (continuous batching, S == 1):
            # scatter row b's new K/V to its own position. Out-of-range
            # positions (retired slots past cache_len) are dropped.
            rows = jnp.arange(B)
            cp = jnp.asarray(cache_pos, jnp.int32)
            ck = cache["k"].at[rows, cp].set(k[:, 0], mode="drop")
            cv = cache["v"].at[rows, cp].set(v[:, 0], mode="drop")
            slot_pos = None
            kv_pos = jnp.broadcast_to(
                jnp.arange(cache_len, dtype=jnp.int32)[None, :], (B, cache_len))
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, cache_pos, 0, 0))
            slot_pos = None
            kv_pos = jnp.broadcast_to(
                jnp.arange(cache_len, dtype=jnp.int32)[None, :], (B, cache_len))
        ck = part.constrain(ck, ("batch", "cache_seq", "kv_heads", None))
        cv = part.constrain(cv, ("batch", "cache_seq", "kv_heads", None))
        new_cache = dict(cache, k=ck, v=cv)
        if slot_pos is not None:
            new_cache["pos"] = slot_pos
        if use_kernel and S == 1 and ring and _decode_kernel_ok(window):
            # ring-cache decode hot path: same resident gather maps, the
            # window mask consults the ring's position stream instead of
            # rotating the buffer (PR 4's logged kernel-path hole)
            from repro.kernels import ops
            rows, inv = _head_rows_or_identity(head_rows, head_inv,
                                               q.shape[2])
            out = ops.decode_attention_ring_bshd(
                q, ck, cv, _decode_lengths(cache_pos, B), slot_pos,
                window=window, rows=rows, inv_rows=inv)
            return _project_out(p, out, part), new_cache
        if use_kernel and S == 1 and window == 0 and slot_pos is None \
                and _decode_kernel_ok(cache_len):
            # linear-cache decode hot path: the Pallas flash-decode kernel
            # over this dispatch's resident head rows (identity = all)
            from repro.kernels import ops
            rows, inv = _head_rows_or_identity(head_rows, head_inv,
                                               q.shape[2])
            out = ops.decode_attention_resident_bshd(
                q, ck, cv, _decode_lengths(cache_pos, B), rows,
                inv_rows=inv)
            return _project_out(p, out, part), new_cache
        mask = causal_mask(positions, kv_pos, window)
        out = attend(ck, cv, kv_pos, mask)
    else:
        mask = causal_mask(positions, positions, window)
        out = attend(k, v, positions, mask)
    return _project_out(p, out, part), new_cache


def cross_attention_block(cfg: ModelConfig, p: dict, hd: HeadDims, x,
                          part: Partitioner, *, kv_embeds=None, kv_cache=None,
                          kv_mask=None, use_kernel: bool = False):
    """Gated cross-attention (llama-3.2-vision).  K/V come either from
    ``kv_embeds`` (B, n_img, D) — projected here and returned as a static
    cache — or from a previously computed ``kv_cache`` {"k","v"}.

    ``use_kernel`` dispatches S == 1 decode to the flash-decode kernel
    with per-row lengths = ``kv_mask.sum(-1)``: the serving engine's image
    buffers are right-padded (valid rows form a prefix), which is exactly
    the kernel's length-masked validity model.  A traced ``kv_mask`` (any
    jitted caller, including the engine) bypasses the eager prefix check
    below, so jitted callers MUST guarantee right-padded masks by
    construction — the engine does.  Fully masked rows are patched to the
    jnp path's value (uniform average of V) so streams match even with a
    trained, nonzero gate."""
    B, S = x.shape[0], x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, wt(p, "wq", x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"]
    if kv_cache is None:
        k = jnp.einsum("bsd,dhk->bshk", kv_embeds, wt(p, "wk", x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", kv_embeds, wt(p, "wv", x.dtype))
        if cfg.qkv_bias:
            k, v = k + p["bk"], v + p["bv"]
        if hd.rep > 1:
            k = jnp.repeat(k, hd.rep, axis=2)
            v = jnp.repeat(v, hd.rep, axis=2)
        k = part.constrain(k, ("batch", "img_seq", "kv_heads", None))
        v = part.constrain(v, ("batch", "img_seq", "kv_heads", None))
        kv_cache = {"k": k, "v": v}
    k, v = kv_cache["k"], kv_cache["v"]
    if use_kernel and S == 1 and _decode_kernel_ok(k.shape[1]):
        from repro.kernels import ops
        I = k.shape[1]
        if kv_mask is None:
            lens = jnp.full((B,), I, jnp.int32)
        else:
            lens = jnp.sum(kv_mask, axis=-1).astype(jnp.int32)
            if not isinstance(kv_mask, jax.core.Tracer):
                # The kernel models validity as a per-row length, so a
                # concrete mask must be prefix-contiguous (right-padded);
                # a scattered mask would silently attend to wrong slots.
                pref = jnp.arange(I, dtype=jnp.int32)[None, :] < lens[:, None]
                if not bool(jnp.all(jnp.asarray(kv_mask, bool) == pref)):
                    raise ValueError(
                        "use_kernel cross-attention needs a prefix "
                        "(right-padded) kv_mask; got a non-contiguous "
                        "validity set — use the jnp path instead")
        rows = jnp.arange(q.shape[2], dtype=jnp.int32)
        out = ops.decode_attention_resident_bshd(q, k, v, lens, rows)
        if kv_mask is not None:
            # Fully-masked rows: the kernel's length model yields 0, but
            # the jnp path softmaxes a uniformly -1e30 score row into the
            # uniform average of V — match it so use_kernel streams stay
            # equal even with a trained (nonzero) gate.
            G = q.shape[2] // v.shape[2]
            vm = jnp.repeat(jnp.mean(v, axis=1), G, axis=1)[:, None]
            out = jnp.where((lens == 0)[:, None, None, None],
                            vm.astype(out.dtype), out)
        return _project_out(p, out, part, gate=p["gate"]), kv_cache
    mask = None if kv_mask is None else kv_mask[:, None, None, None, :]
    out = attention_scores(q, k, v, mask, part)
    return _project_out(p, out, part, gate=p["gate"]), kv_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, dtype=None) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "w_gate": dense_init(ks[0], D, (D, F), dtype),
            "w_up": dense_init(ks[1], D, (D, F), dtype),
            "w_down": dense_init(ks[2], F, (F, D), dtype),
        }
    return {  # gelu
        "w_up": dense_init(ks[0], D, (D, F), dtype),
        "b_up": jnp.zeros((F,), dtype),
        "w_down": dense_init(ks[1], F, (F, D), dtype),
        "b_down": jnp.zeros((D,), dtype),
    }


def mlp_block(cfg: ModelConfig, p: dict, x, part: Partitioner):
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(x @ wt(p, "w_gate", x.dtype)) * (x @ wt(p, "w_up", x.dtype))
        h = part.constrain(h, ("batch", "seq", "d_ff"))
        out = h @ wt(p, "w_down", x.dtype)
    else:
        h = jax.nn.gelu(x @ wt(p, "w_up", x.dtype) + p["b_up"])
        h = part.constrain(h, ("batch", "seq", "d_ff"))
        out = h @ wt(p, "w_down", x.dtype) + p["b_down"]
    return part.constrain(out, ("batch", "res_seq", "d_model"))


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embed(key, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 2)
    p = {"tok_embed": _normal(ks[0], (cfg.vocab_size, cfg.d_model), 0.02, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], cfg.d_model, (cfg.d_model, cfg.vocab_size), dtype)
    return p


def embed(cfg: ModelConfig, p: dict, tokens, part: Partitioner):
    from repro.models.quantization import is_quantized
    tab = p["tok_embed"]
    if is_quantized(tab):
        # gather int8 rows, dequant the gathered rows only
        rows = jnp.take(tab["q8"], tokens, axis=0).astype(jnp.float32)
        x = (rows * tab["sc"]).astype(jnp.dtype(cfg.dtype))
        return part.constrain(x, ("batch", "res_seq", "d_model"))
    x = jnp.take(tab, tokens, axis=0)
    return part.constrain(x, ("batch", "res_seq", "d_model"))


def unembed(cfg: ModelConfig, p: dict, x, part: Partitioner):
    if cfg.tie_embeddings:
        w = wt(p, "tok_embed", x.dtype).T
    else:
        w = wt(p, "lm_head", x.dtype)
    logits = jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=jnp.float32)
    return part.constrain(logits, ("batch", "seq", "vocab"))


def cross_entropy(logits, labels, part: Partitioner):
    """Mean token cross-entropy; logits f32 (B,S,V), labels int (B,S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
