"""Decoder-only transformer LM (dense / MoE / VLM / audio families).

- ``lax.scan`` over stacked layer parameters (compile time & HLO size stay
  O(1) in depth; required for the 80-layer dry-runs).
- KV caches are stacked (L, B, T, KvE, dh) pytrees threaded through the layer
  scan as xs/ys; sliding-window archs (Mixtral) use ring-buffer caches of
  length ``window``.
- VLM (llama-3.2-vision): 40 layers = 8 supergroups of [3 self, 1 cross,
  1 self]; cross-attention K/V are projected once from the (stubbed) image
  embeddings and live in the decode state.
- Optional remat (``jax.checkpoint``) around each layer for training.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import init_moe, moe_block, moe_block_capacity
from repro.models.partitioning import NULL, Partitioner

REMAT_POLICIES = {
    "none": None,
    "full": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}

# decay of the router-load EWMA kept in the decode state ("expert_load"):
# load_t = d*load_{t-1} + (1-d)*freq_t.  The serving engine normalizes and
# feeds it to the controller's expert cost model each interval.
EXPERT_LOAD_EWMA = 0.9


class TransformerLM:
    """Config-driven decoder-only LM."""

    def __init__(self, cfg: ModelConfig, *, tp: int = 1,
                 part: Partitioner = NULL, remat: str = "none",
                 capacity_moe: bool = False, capacity_factor: float = 1.25,
                 use_kernel: bool = False):
        self.cfg = cfg
        self.tp = tp
        self.part = part
        self.hd = L.head_dims(cfg, tp)
        self.remat = remat
        # decode attention via the Pallas flash-decode kernel; the decode
        # state may carry per-layer "head_rows"/"head_inv" gather maps
        # (placement_bridge.head_row_maps) so each layer's kernel grid is
        # the slot-grouped resident slice the controller placed.
        self.use_kernel = use_kernel
        self.capacity_moe = capacity_moe
        self.capacity_factor = capacity_factor
        self.is_vlm = cfg.family == "vlm"
        if self.is_vlm:
            assert cfg.n_layers % 5 == 0
            self.n_groups = cfg.n_layers // 5
        self.window = cfg.sliding_window

    # ------------------------------------------------------------------ init
    def _init_layer(self, key) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        p = {"attn": L.init_attention(ks[0], cfg, self.hd)}
        dt = jnp.dtype(cfg.param_dtype)
        for nm in ("ln1", "ln2"):
            base = L.init_norm(cfg, cfg.d_model, dt)
            p[nm] = base[""]
            if "_b" in base:
                p[nm + "_b"] = base["_b"]
        if cfg.is_moe:
            p["moe"] = init_moe(ks[1], cfg)
        else:
            p["mlp"] = L.init_mlp(ks[1], cfg)
        return p

    def _init_cross_layer(self, key) -> dict:
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        dt = jnp.dtype(cfg.param_dtype)
        p = {"attn": L.init_attention(ks[0], cfg, self.hd, cross=True),
             "mlp": L.init_mlp(ks[1], cfg),
             "gate_ffn": jnp.zeros((), dt)}
        for nm in ("ln1", "ln2"):
            base = L.init_norm(cfg, cfg.d_model, dt)
            p[nm] = base[""]
            if "_b" in base:
                p[nm + "_b"] = base["_b"]
        return p

    def init(self, key) -> dict:
        cfg = self.cfg
        k_emb, k_layers, k_cross, k_f = jax.random.split(key, 4)
        if self.is_vlm:
            sk = jax.random.split(k_layers, 4 * self.n_groups)
            self_keys = sk.reshape((self.n_groups, 4) + sk.shape[1:])
            cross_keys = jax.random.split(k_cross, self.n_groups)
            layers_p = jax.vmap(jax.vmap(self._init_layer))(self_keys)
            cross_p = jax.vmap(self._init_cross_layer)(cross_keys)
            params = {"layers": layers_p, "cross_layers": cross_p}
        else:
            lkeys = jax.random.split(k_layers, cfg.n_layers)
            params = {"layers": jax.vmap(self._init_layer)(lkeys)}
        params.update(L.init_embed(k_emb, cfg))
        fin = L.init_norm(cfg, cfg.d_model, jnp.dtype(cfg.param_dtype))
        params["ln_f"] = fin[""]
        if "_b" in fin:
            params["ln_f_b"] = fin["_b"]
        return params

    def _barrier(self, xs):
        """Pin the per-layer param slice inside the scan body: stops XLA
        from rewriting gather(slice(params,i)) into slice(gather(params))
        and hoisting the FSDP all-gather of the whole stacked layer pytree
        out of the while loop (which materializes all layers' gathered
        weights at once — DESIGN.md §9 / §Perf).  Differentiable (identity
        VJP, layers.pin_layer_slice) so train steps can grad through it."""
        if self.part.mesh is None:
            return xs
        return L.pin_layer_slice(xs)

    # ----------------------------------------------------------------- layer
    def _layer(self, p: dict, x, positions, cache, cache_pos,
               head_rows=None, head_inv=None, page_map=None,
               write_valid=None):
        # named scopes ("norm", "attention" with its "kv_write", "mlp",
        # "lm_head") reach the device trace as the ops' op_name prefixes,
        # so device time can be split by part of the layer
        cfg, part = self.cfg, self.part
        with jax.named_scope("norm"):
            h = L.apply_norm(cfg, p, "ln1", x)
        # explicit SP->TP boundary ON THE BF16 TENSOR: norms run in the
        # sequence-sharded region (pointwise over D), the all-gather happens
        # here rather than on an f32 intermediate chosen by GSPMD
        # (EXPERIMENTS.md §Perf H2-1: halves boundary collective bytes and
        # avoids SPMD "involuntary full rematerialization" reshards).
        h = part.constrain(h, ("batch", "seq", "d_model"))
        with jax.named_scope("attention"):
            attn_out, new_cache = L.self_attention_block(
                cfg, p["attn"], self.hd, h, positions, part,
                cache=cache, cache_pos=cache_pos, window=self.window,
                use_kernel=self.use_kernel, head_rows=head_rows,
                head_inv=head_inv, page_map=page_map,
                write_valid=write_valid)
        x = x + attn_out
        with jax.named_scope("norm"):
            h = L.apply_norm(cfg, p, "ln2", x)
        h = part.constrain(h, ("batch", "seq", "d_model"))
        aux = jnp.zeros((), jnp.float32)
        freq = None
        with jax.named_scope("mlp"):
            if cfg.is_moe:
                if self.capacity_moe:
                    mlp_out, aux, freq = moe_block_capacity(
                        cfg, p["moe"], h, part, self.capacity_factor)
                else:
                    mlp_out, aux, freq = moe_block(cfg, p["moe"], h, part)
            else:
                mlp_out = L.mlp_block(cfg, p["mlp"], h, part)
        return x + mlp_out, new_cache, aux, freq

    def _cross_layer(self, p: dict, x, img_kv, img_mask):
        cfg, part = self.cfg, self.part
        h = L.apply_norm(cfg, p, "ln1", x)
        attn_out, _ = L.cross_attention_block(cfg, p["attn"], self.hd, h, part,
                                              kv_cache=img_kv, kv_mask=img_mask,
                                              use_kernel=self.use_kernel)
        x = x + attn_out
        h = L.apply_norm(cfg, p, "ln2", x)
        mlp_out = L.mlp_block(cfg, p["mlp"], h, part)
        return x + mlp_out * jnp.tanh(p["gate_ffn"]).astype(x.dtype)

    def _project_img_kv(self, params, img_embeds):
        """vmap K/V projection over the 8 cross layers -> (G,B,I,KvE,dh)."""
        def proj(p):
            from repro.models.quantization import wt
            k = jnp.einsum("bsd,dhk->bshk", img_embeds,
                           wt(p["attn"], "wk", img_embeds.dtype))
            v = jnp.einsum("bsd,dhk->bshk", img_embeds,
                           wt(p["attn"], "wv", img_embeds.dtype))
            if self.cfg.qkv_bias:
                k, v = k + p["attn"]["bk"], v + p["attn"]["bv"]
            if self.hd.rep > 1:
                k = jnp.repeat(k, self.hd.rep, axis=2)
                v = jnp.repeat(v, self.hd.rep, axis=2)
            return {"k": k, "v": v}
        return jax.vmap(proj)(params["cross_layers"])

    # --------------------------------------------------------------- forward
    def _run_layers(self, params, x, positions, cache, cache_pos,
                    img_kv=None, img_mask=None, head_rows=None,
                    head_inv=None, page_map=None, write_valid=None):
        """Scan over layers. cache: stacked {"k","v"[,"pos"]} or None.
        ``head_rows``/``head_inv``: stacked (n_layers, Hp) kernel gather/
        scatter maps scanned alongside the cache, so layer l's decode
        dispatch reads layer l's resident-slice row map (dense archs only
        — VLM caches are (G, 4, ...) stacks whose migrations are
        all-layers-equal, so identity maps stay correct there).
        ``page_map``/``write_valid`` (paged caches) are CLOSURES over the
        scan, not scanned: one page table serves every layer — the layer
        axis lives in the page store, not the table."""
        remat_policy = REMAT_POLICIES[self.remat]

        def body(carry, xs):
            x, aux = carry
            xs = self._barrier(xs)
            if self.is_vlm:
                (self_p, cross_p, kv) = xs
                for i in range(3):
                    sp = jax.tree.map(lambda a, i=i: a[i], self_p)
                    x, _, a, _ = self._layer(sp, x, positions, None, cache_pos)
                    aux += a
                x = self._cross_layer(cross_p, x, kv, img_mask)
                sp = jax.tree.map(lambda a: a[3], self_p)
                x, _, a, _ = self._layer(sp, x, positions, None, cache_pos)
                return (x, aux + a), None
            layer_p, layer_cache, rows, inv = xs
            x, new_cache, a, f = self._layer(layer_p, x, positions,
                                             layer_cache, cache_pos, rows,
                                             inv, page_map=page_map,
                                             write_valid=write_valid)
            if self.cfg.is_moe:
                return (x, aux + a), (new_cache, f)
            return (x, aux + a), new_cache

        if self.remat != "none":
            body = jax.checkpoint(body, policy=remat_policy,
                                  prevent_cse=False)

        aux0 = jnp.zeros((), jnp.float32)
        if self.is_vlm:
            if cache is not None:
                return self._run_layers_vlm_cached(params, x, positions, cache,
                                                   cache_pos, img_kv, img_mask,
                                                   body)
            xs = (params["layers"], params["cross_layers"], img_kv)
            (x, aux), _ = jax.lax.scan(body, (x, aux0), xs)
            return x, None, aux, None
        xs = (params["layers"], cache, head_rows, head_inv)
        if self.cfg.is_moe:
            # ys carry the per-layer routed-token fractions alongside the
            # cache -> stacked (L, E) router-load observation
            (x, aux), (new_cache, freqs) = jax.lax.scan(body, (x, aux0), xs)
            return x, new_cache, aux, freqs
        (x, aux), new_cache = jax.lax.scan(body, (x, aux0), xs)
        return x, new_cache, aux, None

    def _run_layers_vlm_cached(self, params, x, positions, cache, cache_pos,
                               img_kv, img_mask, _body_unused):
        """VLM with self-attn KV caches: 4 self caches per group."""
        def body(carry, xs):
            x, aux = carry
            xs = self._barrier(xs)
            self_p, cross_p, kv, self_cache = xs
            new_caches = []
            for i in range(3):
                sp = jax.tree.map(lambda a, i=i: a[i], self_p)
                lc = jax.tree.map(lambda a, i=i: a[i], self_cache)
                x, nc, a, _ = self._layer(sp, x, positions, lc, cache_pos)
                new_caches.append(nc)
                aux += a
            x = self._cross_layer(cross_p, x, kv, img_mask)
            sp = jax.tree.map(lambda a: a[3], self_p)
            lc = jax.tree.map(lambda a: a[3], self_cache)
            x, nc, a, _ = self._layer(sp, x, positions, lc, cache_pos)
            new_caches.append(nc)
            stacked = jax.tree.map(lambda *ts: jnp.stack(ts), *new_caches)
            return (x, aux + a), stacked

        xs = (params["layers"], params["cross_layers"], img_kv, cache)
        (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
        return x, new_cache, aux, None

    def forward(self, params, tokens, *, img_embeds=None, img_mask=None):
        """Full-sequence forward (training / no-cache prefill). Returns
        (logits, aux_loss)."""
        cfg, part = self.cfg, self.part
        B, S = tokens.shape
        x = L.embed(cfg, params, tokens, part)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        img_kv = None
        if self.is_vlm:
            img_kv = self._project_img_kv(params, img_embeds)
        x, _, aux, _ = self._run_layers(params, x, positions, None, None,
                                        img_kv=img_kv, img_mask=img_mask)
        x = L.apply_norm(cfg, params, "ln_f", x)
        logits = L.unembed(cfg, params, x, part)
        return logits, aux

    def loss(self, params, batch) -> jnp.ndarray:
        logits, aux = self.forward(params, batch["tokens"],
                                   img_embeds=batch.get("img_embeds"),
                                   img_mask=batch.get("img_mask"))
        ce = L.cross_entropy(logits, batch["labels"], self.part)
        return ce + 0.01 * aux

    # ----------------------------------------------------------------- cache
    def cache_len(self, max_seq: int) -> int:
        return min(max_seq, self.window) if self.window else max_seq

    def init_cache(self, batch: int, max_seq: int, dtype=None) -> dict:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        T = self.cache_len(max_seq)
        lead = (self.n_groups, 4) if self.is_vlm else (cfg.n_layers,)
        shape_k = lead + (batch, T, self.hd.KvE, self.hd.dh)
        ring = bool(self.window and T == self.window)
        if cfg.kv_quant and not ring:
            # int8 KV cache with per-(token, head) scales (§Perf): halves
            # the resident cache; dequant happens at the attention read.
            cache = {"k": jnp.zeros(shape_k, jnp.int8),
                     "v": jnp.zeros(shape_k, jnp.int8),
                     "k_sc": jnp.zeros(lead + (batch, T, self.hd.KvE),
                                       jnp.float32),
                     "v_sc": jnp.zeros(lead + (batch, T, self.hd.KvE),
                                       jnp.float32)}
            return cache
        cache = {"k": jnp.zeros(shape_k, dtype), "v": jnp.zeros(shape_k, dtype)}
        if ring:
            cache["pos"] = jnp.full(lead + (T,), jnp.int32(-2**30))
        return cache

    def init_decode_state(self, params, batch: int, max_seq: int, *,
                          prompt=None, img_embeds=None, img_mask=None,
                          dtype=None, per_slot: bool = False) -> Dict[str, Any]:
        """``per_slot=True`` keeps one position per batch row (continuous
        batching): decode advances each slot independently and prefills can
        land rows at different depths via :meth:`insert_slot`."""
        pos0 = jnp.zeros((batch,), jnp.int32) if per_slot \
            else jnp.zeros((), jnp.int32)
        state: Dict[str, Any] = {"cache": self.init_cache(batch, max_seq, dtype),
                                 "pos": pos0}
        if self.cfg.is_moe:
            # router-load EWMA, uniform prior; decode_step folds each step's
            # observed routed-token fractions in (EXPERT_LOAD_EWMA decay)
            E = self.cfg.n_experts
            state["expert_load"] = jnp.full(
                (self.cfg.n_layers, E), 1.0 / E, jnp.float32)
        if self.is_vlm:
            state["img_kv"] = self._project_img_kv(params, img_embeds)
            state["img_mask"] = img_mask
        return state

    def prefill(self, params, state, tokens):
        """Run the prompt through the model, filling caches. Returns
        (last-token logits, state)."""
        cfg, part = self.cfg, self.part
        B, S = tokens.shape
        x = L.embed(cfg, params, tokens, part)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x, new_cache, _, _ = self._run_layers(
            params, x, positions, state["cache"], jnp.zeros((), jnp.int32),
            img_kv=state.get("img_kv"), img_mask=state.get("img_mask"))
        x = L.apply_norm(cfg, params, "ln_f", x)
        logits = L.unembed(cfg, params, x[:, -1:, :], part)
        return logits[:, 0], dict(state, cache=new_cache,
                                  pos=jnp.asarray(S, jnp.int32))

    def decode_step(self, params, state, tokens):
        """One autoregressive step. tokens: (B,) int32. Returns (logits (B,V),
        new state).

        ``state["pos"]`` is either the shared scalar position (lock-step
        batch) or a (B,) vector (per-slot continuous batching): each row
        embeds/attends/writes at its own depth, so slots prefilled at
        different times decode together.
        """
        cfg, part = self.cfg, self.part
        B = tokens.shape[0]
        pos = state["pos"]
        per_slot = getattr(pos, "ndim", 0) == 1
        x = L.embed(cfg, params, tokens[:, None], part)
        if per_slot:
            positions = pos[:, None].astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
        page_map = state.get("page_map")
        x, new_cache, _, freqs = self._run_layers(
            params, x, positions, state["cache"], pos,
            img_kv=state.get("img_kv"), img_mask=state.get("img_mask"),
            head_rows=state.get("head_rows"), head_inv=state.get("head_inv"),
            page_map=page_map)
        with jax.named_scope("norm"):
            x = L.apply_norm(cfg, params, "ln_f", x)
        with jax.named_scope("lm_head"):
            logits = L.unembed(cfg, params, x, part)
        if per_slot:
            # clamp retired slots at the cache edge (their writes drop);
            # the paged extent is the page table's logical span, not a
            # dense cache axis
            if page_map is not None:
                T = page_map.shape[1] * state["cache"]["k"].shape[2]
            else:
                T = state["cache"]["k"].shape[-3]
            new_pos = jnp.minimum(pos + 1, jnp.int32(T))
        else:
            new_pos = pos + 1
        new_state = dict(state, cache=new_cache, pos=new_pos)
        if freqs is not None and "expert_load" in state:
            d = jnp.float32(EXPERT_LOAD_EWMA)
            new_state["expert_load"] = (d * state["expert_load"]
                                        + (1.0 - d) * freqs)
        return logits[:, 0], new_state

    # ----------------------------------------------- continuous batching
    def prefill_bucketed(self, params, state, tokens, length):
        """Prefill right-padded prompts: ``tokens`` (B, Lb) padded to a
        bucket length, ``length`` (B,) true prompt lengths.  Returns the
        logits of each row's LAST REAL token and a per-slot state with
        ``pos == length``.  Padding rows write garbage K/V at indices
        >= length, but the causal mask hides index q until decode step q
        overwrites it first, so the garbage is never attended.  Compiles
        once per bucket length Lb, not per prompt length."""
        cfg, part = self.cfg, self.part
        B, S = tokens.shape
        x = L.embed(cfg, params, tokens, part)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        x, new_cache, _, _ = self._run_layers(
            params, x, positions, state["cache"], jnp.zeros((), jnp.int32),
            img_kv=state.get("img_kv"), img_mask=state.get("img_mask"))
        x = L.apply_norm(cfg, params, "ln_f", x)
        last = jnp.take_along_axis(
            x, jnp.maximum(length - 1, 0)[:, None, None].astype(jnp.int32),
            axis=1)                                      # (B, 1, D)
        logits = L.unembed(cfg, params, last, part)
        return logits[:, 0], dict(state, cache=new_cache,
                                  pos=jnp.asarray(length, jnp.int32))

    def insert_slot(self, state, sub, slot):
        """Copy a batch-1 prefilled ``sub`` state (cache length Lb <= T)
        into batch row ``slot`` of a persistent per-slot decode state:
        the slot-manager write of continuous batching.  ``slot`` may be a
        traced scalar — one compile serves every slot.

        K/V buffers carry their batch axis at ``ndim - 4`` (dense
        (L,B,T,KvE,dh) -> axis 1, VLM self caches (G,4,B,T,KvE,dh) -> axis
        2, VLM ``img_kv`` (G,B,I,KvE,dh) -> axis 1), so one splice rule
        covers every cache layout; VLM states additionally splice the
        request's static image K/V and mask rows."""
        slot = jnp.asarray(slot, jnp.int32)

        def splice_kv(dst, src, batch_axis):
            start = tuple(slot if a == batch_axis else jnp.int32(0)
                          for a in range(dst.ndim))
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), start)

        cache, sub_cache = state["cache"], sub["cache"]
        upd = {name: splice_kv(cache[name], sub_cache[name],
                               cache[name].ndim - 4)
               for name in ("k", "v")}
        # int8 KV caches carry per-(token, head) scales whose batch axis
        # sits one dim closer to the front ((L, B, T, KvE) -> ndim - 3);
        # splicing values without their scales would dequantize garbage
        for name in ("k_sc", "v_sc"):
            if name in cache:
                upd[name] = splice_kv(cache[name], sub_cache[name],
                                      cache[name].ndim - 3)
        pos = jax.lax.dynamic_update_slice(
            state["pos"], jnp.asarray(sub["pos"], jnp.int32), (slot,))
        out = dict(state, cache=dict(cache, **upd), pos=pos)
        if "img_kv" in state and "img_kv" in sub:
            img = state["img_kv"]
            out["img_kv"] = dict(img, **{
                name: splice_kv(img[name], sub["img_kv"][name],
                                img[name].ndim - 4)
                for name in ("k", "v")})
        if state.get("img_mask") is not None and \
                sub.get("img_mask") is not None:
            out["img_mask"] = jax.lax.dynamic_update_slice(
                state["img_mask"],
                jnp.asarray(sub["img_mask"], state["img_mask"].dtype),
                (slot, jnp.int32(0)))
        return out

    # ------------------------------------------------------- paged caching
    def init_paged_cache(self, n_pages: int, page_size: int,
                         dtype=None) -> dict:
        """Pooled page store: stacked (L, n_pages, P, KvE, dh) — the
        batch × seq extent of the dense cache is replaced by a flat page
        axis shared by every slot, so resident bytes follow ALLOCATED
        pages, not ``n_slots * max_seq`` worst case.  int8-KV configs
        page their per-(token, head) scales alongside the values."""
        cfg = self.cfg
        if self.window:
            raise NotImplementedError(
                "paged caches are linear; sliding-window archs keep the "
                "ring cache")
        if self.is_vlm:
            raise NotImplementedError(
                "paged caches do not yet carry the VLM image K/V")
        dtype = dtype or jnp.dtype(cfg.dtype)
        lead = (cfg.n_layers,)
        shape = lead + (n_pages, page_size, self.hd.KvE, self.hd.dh)
        if cfg.kv_quant:
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "k_sc": jnp.zeros(
                        lead + (n_pages, page_size, self.hd.KvE),
                        jnp.float32),
                    "v_sc": jnp.zeros(
                        lead + (n_pages, page_size, self.hd.KvE),
                        jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def init_paged_state(self, params, batch: int, n_pages: int,
                         page_size: int, pages_per_slot: int,
                         dtype=None) -> Dict[str, Any]:
        """Per-slot paged decode state: the page store, per-row positions,
        and the (batch, pages_per_slot) page table — all ``-1``
        (unmapped) until the engine mounts an allocation."""
        return {"cache": self.init_paged_cache(n_pages, page_size, dtype),
                "pos": jnp.zeros((batch,), jnp.int32),
                "page_map": jnp.full((batch, pages_per_slot), -1,
                                     jnp.int32)}

    def prefill_paged(self, params, state, tokens, row, start, length):
        """ONE fixed-shape chunk of a paged prefill: ``tokens`` (1, C)
        holds chunk tokens right-padded to the chunk size, ``row`` the
        slot row, ``start`` the chunk's absolute start position and
        ``length`` its valid token count — ALL traced scalars, so every
        chunk of every prompt in every slot runs the same single
        lowering (no bucket ladder).  K/V land in the slot's mapped pages
        (invalid tail writes drop); returns the logits of the chunk's
        last VALID token (meaningful on the final chunk) and the state
        with ``pos[row] = start + length``."""
        cfg, part = self.cfg, self.part
        B, C = tokens.shape
        row = jnp.asarray(row, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        length = jnp.asarray(length, jnp.int32)
        x = L.embed(cfg, params, tokens, part)
        positions = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
        valid = (jnp.arange(C, dtype=jnp.int32) < length)[None, :]
        page_row = jax.lax.dynamic_slice_in_dim(
            state["page_map"], row, 1, axis=0)            # (1, np)
        x, new_cache, _, _ = self._run_layers(
            params, x, positions, state["cache"], None,
            page_map=page_row, write_valid=valid)
        with jax.named_scope("norm"):
            x = L.apply_norm(cfg, params, "ln_f", x)
        last = jnp.take_along_axis(
            x, jnp.maximum(length - 1, 0)[None, None, None], axis=1)
        with jax.named_scope("lm_head"):
            logits = L.unembed(cfg, params, last, part)
        pos = jax.lax.dynamic_update_slice(
            state["pos"], (start + length)[None], (row,))
        return logits[:, 0], dict(state, cache=new_cache, pos=pos)

    def mount_slot_pages(self, state, row, pages, pos):
        """Write slot ``row``'s page-table row (+ position) into a paged
        decode state — the paged analog of :meth:`insert_slot`, used at
        admission, page-boundary extension, and retire (all ``-1`` +
        pos 0: the row's writes drop and its reads are masked).  ``row``
        stays a traced scalar so ONE lowering serves every slot."""
        row = jnp.asarray(row, jnp.int32)
        pm = jax.lax.dynamic_update_slice(
            state["page_map"], jnp.asarray(pages, jnp.int32)[None, :],
            (row, jnp.int32(0)))
        ps = jax.lax.dynamic_update_slice(
            state["pos"], jnp.asarray(pos, jnp.int32)[None], (row,))
        return dict(state, page_map=pm, pos=ps)
