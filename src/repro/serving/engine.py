"""Serving engines with the paper's controller in the loop.

Two schedulers over the same model/controller stack:

``ServingEngine`` (continuous batching, the production path)
  A persistent ``(n_slots, max_seq)`` KV cache with per-slot positions.
  Any queued request is admitted into any free slot the moment one frees:
  the prompt is right-padded to a small set of bucketed lengths (so prefill
  JIT recompiles stay bounded), prefilled at batch 1, and spliced into the
  slot's cache row (``insert_slot``).  Decode runs one step for the whole
  batch with per-slot attention masking, so slots at different sequence
  depths generate together — no equal-prompt-length restriction and no
  wave barrier.  This is the slot-based decode path production systems use
  (MaxText-style prefill-then-insert; Pope et al. 2022).

``WaveServingEngine`` (the old static scheduler, kept as the baseline)
  Up to ``n_slots`` equal-length prompts form a wave; the wave prefills as
  one batch and decodes in lock-step until every request finishes.  Freed
  slots stay dead until the wave drains and each new prompt length costs a
  fresh prefill compile — ``benchmarks/serving_throughput.py`` quantifies
  the gap.

``ServingEngine(pipeline_k=K)`` adds cross-device decode pipelining: slots
split into K groups with independent decode states and each step advances
one group, so K different requests' tokens are in flight across
layer-disjoint placement stages (delay.pipelined_inference_delay prices
the overlap; benchmarks/pipelined_decode.py measures it).  GQA archs now
migrate *physically* at KV-group granularity (group-consistent
permutations from placement_bridge), and VLM decode states are slot-wired
(per-request image K/V spliced by insert_slot) — both former skip paths.

Every λ generated tokens (λ·pipeline_k scheduler steps) the
IntervalController observes step-time telemetry
plus the *actual* per-slot cache occupancy, re-runs Algorithm 1, and
applies head migrations to weights AND cache in the inter-step gap — the
paper's per-interval migration loop as a production serving feature.
Under continuous batching the migrated cache holds slots at unequal
positions, the realistic version of §III.D's loop.

On a single CPU host this runs unsharded (NULL partitioner) and the
controller drives a *simulated* slot network — the same code path the TPU
deployment uses with mesh slots.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.blocks import CostModel
from repro.core.controller import ControllerConfig, IntervalController
from repro.core.network import DeviceNetwork
from repro.models.api import build_model
from repro.runtime.fault_tolerance import HeartbeatMonitor
from repro.runtime.spans import SpanRecorder


class UnsupportedArchError(NotImplementedError):
    """Raised at ENGINE CONSTRUCTION for architectures the slot-level
    scheduler cannot serve — never mid-serve: by the time requests flow,
    the config has already been vetted.  Subclasses NotImplementedError so
    pre-existing callers' except clauses keep working."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (L0,) int32
    max_new_tokens: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0
    t_admit: float = 0.0          # popped from the queue into a slot
    t_first: float = 0.0
    t_done: float = 0.0
    img: Optional[np.ndarray] = None       # (I, D) VLM patch embeddings
    img_mask: Optional[np.ndarray] = None  # (I,) bool


def supports_continuous(cfg: ModelConfig,
                        max_seq: Optional[int] = None) -> Optional[str]:
    """None when ``cfg`` can run the slot-level scheduler, else the reason
    it can't (cfg-only, so ``make_engine`` decides before building params).
    VLM states are slot-wired (img_kv/img_mask splice in
    ``TransformerLM.insert_slot``), and int8 KV caches (``kv_quant``) are
    continuous too — ``insert_slot`` splices the quantized values AND
    their per-(token, head) scales, and decode scatters per-slot writes
    into the int8 buffers — so neither falls back any more.

    Sliding-window archs (Mixtral) allocate a ring cache only when the
    served extent reaches the window (``init_cache``: T = min(max_seq,
    window)); serving with ``max_seq`` STRICTLY below the window keeps the
    cache linear, so the slot scheduler — and with it continuous MoE
    serving with applied expert migrations — applies.  Callers that don't
    know the extent yet (``max_seq=None``) get the conservative reject."""
    if cfg.family in ("ssm", "hybrid"):
        return f"{cfg.family} archs have no prefill_bucketed/insert_slot API"
    if cfg.sliding_window and (max_seq is None
                               or max_seq >= cfg.sliding_window):
        return ("continuous batching needs a linear KV cache, not a ring; "
                f"serve with max_seq < sliding_window "
                f"({cfg.sliding_window}) to keep the cache linear")
    return None


def default_buckets(max_seq: int, lo: int = 8) -> List[int]:
    """Power-of-two prompt buckets up to ``max_seq``: the prefill compile
    count is bounded by len(buckets), not by the number of distinct prompt
    lengths."""
    out, b = [], lo
    while b < max_seq:
        out.append(b)
        b *= 2
    out.append(max_seq)
    return sorted(set(out))


class _EngineBase:
    """Model + controller wiring and the PRNG-disciplined sampler shared by
    both schedulers."""

    def __init__(self, cfg: ModelConfig, *, n_slots: int = 4,
                 max_seq: int = 512, lam: int = 16, seed: int = 0,
                 net: Optional[DeviceNetwork] = None, cost_cfg=None,
                 part=None, tp: int = 1, greedy: bool = True,
                 layer_mode: str = "graph", pipeline_k: int = 1,
                 use_kernel: bool = False, search: str = "rescoring",
                 cost_page_size: int = 0):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.greedy = greedy
        self.pipeline_k = max(1, int(pipeline_k))
        # search="bottleneck" (with pipeline_k > 1): controller plans come
        # from the bottleneck-targeted placement search, so the real cache/
        # weight migrations below follow the steady-state objective.
        self.search = search
        # use_kernel: decode attention runs the Pallas flash-decode kernel
        # (auto-interpreted on CPU) with its grid derived from the
        # controller's placement — see _refresh_head_rows.
        self.use_kernel = use_kernel
        from repro.models.partitioning import NULL
        self.model = build_model(cfg, tp=tp, part=part or NULL,
                                 use_kernel=use_kernel)
        self.params = self.model.init(jax.random.PRNGKey(seed))
        if cfg.is_moe and isinstance(self.params.get("layers"), dict) \
                and "moe" in self.params["layers"]:
            # identity physical-expert maps: expert migrations permute the
            # weight rows AND these maps; the combine scatters rows back to
            # logical order (models.moe), so installing identity here is a
            # bit-exact no-op until the first expert migration
            from repro.models.moe import expert_identity
            own, sh = expert_identity(cfg.n_experts, cfg.n_layers)
            self.params["layers"]["moe"] = dict(
                self.params["layers"]["moe"], owner=own, share=sh)
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self._rid = 0
        # per-token stream hook: ``token_sink(req, tok, done)`` fires on
        # every generated token (done=False) and once at retire
        # (tok=None, done=True) — the async runtime routes these into
        # per-request streams and the workload driver timestamps them.
        # Purely observational: None (the default) changes nothing.
        self.token_sink: Optional[Callable[[Request, Optional[int], bool],
                                           None]] = None
        # load-signal marks: arrivals/steps since the last interval, so
        # the controller sees the observed arrival rate (requests per
        # scheduler step) and queue depth, not just slot occupancy.
        self._load_mark_step = 0
        self._load_mark_rid = 0
        # controller wiring (the paper's technique in the serving loop).
        # The controller's cost model can use the FULL production dims
        # (cost_cfg) while a reduced model serves on CPU — the placement
        # problem is the production one either way.
        n_dev = net.n_devices if net is not None else max(tp, 4)
        self.net = net or DeviceNetwork.sample(n_dev, seed=seed + 1)
        hd = getattr(self.model, "hd", None)
        n_heads = (hd.Hp if hd and hd.Hp else max(cfg.n_heads, 1))
        heads_per_slot = max(1, n_heads // self.net.n_devices)
        ccfg = cost_cfg or cfg
        # "graph" (default): the controller places the per-layer block
        # graph of the ACTUAL model depth, so its per-layer permutations
        # align 1:1 with the stacked cache/params; cost_cfg still sets the
        # pricing dims (d_model).  "columns" keeps the old aggregate lift
        # at cost_cfg's layer count.
        n_l = cfg.n_layers if layer_mode == "graph" else ccfg.n_layers
        # MoE archs: the controller places per-expert blocks (router-load-
        # weighted compute, weight-only migration bytes) when the expert
        # count tiles the mesh; otherwise the cost model stays expert-
        # oblivious (dense ffn block) rather than emitting perms that
        # cannot be physically applied to the weight stacks.
        n_exp = cfg.n_experts if (cfg.is_moe and cfg.n_experts >= 2
                                  and cfg.n_experts
                                  % self.net.n_devices == 0) else 0
        self.cost = CostModel(d_model=ccfg.d_model, n_heads=max(cfg.n_heads, 1),
                              L0=8, n_layers=max(n_l, 1), lam=lam,
                              compute_mode="incremental",
                              layer_mode=layer_mode,
                              n_experts=n_exp,
                              d_ff=(ccfg.d_ff if n_exp else 0),
                              page_size=max(0, int(cost_page_size)))
        # KV-group size: GQA stacks migrate whole groups (query heads move
        # with their shared KV head), so the controller emits
        # group-consistent permutations — the old silent skip is gone.
        # With replicated KV (hd.rep > 1: tp > n_kv_heads) the unit is the
        # SUPERGROUP Hp // Kp — all query heads of one un-replicated KV
        # head move together, so the Kp-row kv weights stay permutable and
        # the KvE replicated cache rows follow via ``expand_kv_perms``.
        # For rep == 1 this is exactly hd.groups (Hp // KvE), unchanged.
        # Geometry must divide at CONSTRUCTION (never mid-serve): the
        # bridge's head-position space is n_devices·heads_per_slot wide and
        # group blocks must tile it exactly.
        group = (hd.Hp // hd.Kp) if hd and hd.Hp and hd.Kp else 1
        if group > 1 and ((self.net.n_devices * heads_per_slot) % group
                          or max(cfg.n_heads, 1) % group):
            raise UnsupportedArchError(
                f"{cfg.name}: KV group size {group} does not tile the "
                f"{self.net.n_devices}x{heads_per_slot} head-slot geometry "
                f"— pick a device count whose head positions are a "
                f"multiple of the group size")
        # spans of the served path (runtime.spans): off unless enabled or
        # a profiler trace runs; the controller records into the same one
        self.spans = SpanRecorder()
        self.controller = IntervalController(
            max(cfg.n_heads, 1), self.cost, self.net,
            ControllerConfig(lam=lam, heads_per_slot=heads_per_slot,
                             group_size=group,
                             pipeline_k=self.pipeline_k,
                             search=self.search), spans=self.spans)
        self.monitor = HeartbeatMonitor(self.net.n_devices)
        self.lam = lam
        self.decode_steps = 0
        self.migration_log: List[dict] = []
        # Hot-path jits DONATE their state argument: the decode state's
        # KV cache is then input/output-aliased by XLA instead of
        # materializing a second full cache every step — the cache is
        # exactly the per-device memory Algorithm 1 partitions, so an
        # undonated buffer silently doubles it.  The HLO pass of
        # ``python -m repro.analysis`` asserts the aliasing (and zero
        # full-cache parameter copies) on the optimized decode HLO; the
        # caller contract is that every donated state is dead after the
        # call (all call sites reassign, see step()/_admit()).
        self._decode_jit = jax.jit(self.model.decode_step,
                                   donate_argnums=(1,))
        self._prefill_jit = jax.jit(self.model.prefill,
                                    donate_argnums=(1,))
        # sampler: one fresh fold_in key per _sample call — the post-prefill
        # sample and the first post-decode sample can no longer collide on
        # the same PRNGKey(decode_steps) counter value.
        self._sample_base = jax.random.PRNGKey(seed + 0x5EED)
        self.sample_count = 0
        # bounded: one entry per non-greedy sample would otherwise grow for
        # the life of a long-running engine (observability, read by tests)
        self.sample_key_log: Deque[tuple] = collections.deque(maxlen=4096)

    # ---------------------------------------------------------------- intake
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32) -> int:
        req = Request(self._rid, np.asarray(prompt, np.int32),
                      max_new_tokens, t_submit=time.monotonic())
        self._rid += 1
        self.queue.append(req)
        return req.rid

    # --------------------------------------------------------------- sampler
    def _next_sample_key(self):
        key = jax.random.fold_in(self._sample_base, self.sample_count)
        self.sample_count += 1
        try:
            data = jax.random.key_data(key)
        except TypeError:            # legacy uint32 keys
            data = key
        self.sample_key_log.append(tuple(np.asarray(data).ravel().tolist()))
        return key

    def _sample(self, logits: jnp.ndarray) -> np.ndarray:
        if self.greedy:
            return np.asarray(jnp.argmax(logits, axis=-1))
        return np.asarray(jax.random.categorical(self._next_sample_key(),
                                                 logits))

    # -------------------------------------------------------------- streaming
    def _emit_token(self, req: Request, tok: int):
        """Append one generated token and fire the stream hook — the ONE
        place tokens enter a request, so every scheduler path (admission
        sample, decode step, wave loop) streams identically."""
        req.out_tokens.append(tok)
        if self.token_sink is not None:
            self.token_sink(req, tok, False)

    def _emit_done(self, req: Request):
        if self.token_sink is not None:
            self.token_sink(req, None, True)

    # ------------------------------------------------------------- telemetry
    def _record_step(self, dt: float):
        # only live devices heartbeat: a failed device must stay silent in
        # the monitor (its availability estimate is pinned at zero) until
        # rejoin_device revives it
        for j in self.net.active_ids:
            self.monitor.record_step(j, dt)

    def _load_signal(self) -> tuple:
        """(observed arrival rate, queue depth) since the last interval:
        arrivals per *scheduler step* — clock-free, so it is identical on
        virtual and wall clocks — plus the current backlog.  Resets the
        marks, so each interval reports its own window."""
        steps = self.decode_steps - self._load_mark_step
        arrived = self._rid - self._load_mark_rid
        self._load_mark_step = self.decode_steps
        self._load_mark_rid = self._rid
        return arrived / max(steps, 1), len(self.queue)

    # --------------------------------------------------------------- interval
    def _interval_plan(self, tau_tokens: Optional[int] = None) -> dict:
        """Observe -> Algorithm 1: one migration plan per interval.
        ``tau_tokens`` anchors the cost model to the observed decode stream
        (mean slot occupancy, in tokens — in-flight depth never changes
        this conversion, only the *cadence* at which intervals fire).
        The observed arrival rate and queue depth ride along into the
        interval record, so the controller sees LOAD, not just occupancy
        (the honest signal traffic-adaptive search will consume).
        ``plan["plan_s"]`` is the host time this took: the decode stall
        the controller costs per interval (the ``ctl.interval`` span's
        boundaries when spans are on)."""
        span = self.spans.span
        with span("ctl.interval") as iv:
            t0 = iv.t0 if iv else time.monotonic()
            with span("ctl.observe"):
                self.net.step_background_load()
                # close the fault-tolerance loop: C_j(τ) comes from the
                # heartbeat monitor's step-time EWMAs scaling the
                # background-load estimate.  Uniform step times leave the
                # estimate untouched (ratio 1), so a churn-free run
                # observes exactly what direct observation would;
                # hung/failed slots estimate to zero.
                self.controller.observe_monitor(
                    self.monitor, peak_flops=self.net.compute_avail)
                rate, depth = self._load_signal()
            plan = self.controller.step_interval(
                tau=self._tau_of(tau_tokens), arrival_rate=rate,
                queue_depth=depth)
        plan["plan_s"] = (iv.t1 if iv else time.monotonic()) - t0
        return plan

    def _tau_of(self, tau_tokens: Optional[int]) -> Optional[int]:
        """Occupancy (tokens) -> interval index τ of the cost model."""
        if tau_tokens is None:
            return None
        return max(1, round((tau_tokens - self.cost.L0)
                            / max(self.cost.lam, 1)))

    def _migrate_state(self, state, plan, permute_params: bool = True):
        """Execute ``plan`` physically on one decode state: permute weights
        AND caches by the same (group-consistent) per-layer head
        permutations — attention is permutation-equivariant over heads
        (GQA: over whole KV groups) within each layer, so the model
        function is invariant while the placement changes
        (placement_bridge).  Returns (state, applied, reason): every
        not-applied path is reported, never silently skipped.

        ``permute_params=False`` skips the (shared) weight permutation —
        callers holding several decode states for one set of params (the
        pipelined engine's in-flight groups) permute weights exactly once
        per plan."""
        hd = getattr(self.model, "hd", None)
        if hd is None or not hd.Hp:
            return state, False, "model has no addressable attention heads"
        # Migration granularity: the supergroup Hp // Kp (== hd.groups for
        # rep == 1).  For replicated KV (rep > 1) the controller's perms
        # are supergroup-consistent, so q-side weights permute by head
        # rows, kv-side weights by the induced Kp-row permutation, and the
        # KvE replicated cache rows by its rep-expansion — every replica
        # moves with its KV head, which is what makes rep>1 plans
        # applicable at all (they used to be reported-but-skipped).
        G = hd.Hp // hd.Kp if hd.Kp else 1
        rep = hd.rep
        cache = state.get("cache")
        if not (isinstance(cache, dict) and "k" in cache
                and cache["k"].ndim >= 4):
            return state, False, "state has no addressable KV cache"
        from repro.core.placement_bridge import (
            apply_layer_head_perms, expand_kv_perms, kv_group_perms,
            permute_model_heads, permute_model_heads_layers, relative_perms)
        rel = relative_perms(plan["prev_perms"], plan["perms"])
        # per-layer rows only map onto a cache whose LEADING axis is the
        # layer stack (dense (L,B,T,KvE,dh)); grouped stacks (VLM
        # (G,4,...)) must not be reshaped against n_layers
        per_layer = rel.shape[0] > 1 and cache["k"].ndim >= 5 \
            and cache["k"].shape[0] == rel.shape[0]
        new = dict(cache)
        if per_layer:
            # row l migrates layer l independently
            if permute_params:
                self.params = permute_model_heads_layers(self.params, rel,
                                                         group_size=G)
            new["k"], new["v"] = apply_layer_head_perms(
                cache["k"], cache["v"], rel,
                layer_axis=0, head_axis=-2, group_size=G, rep=rep)
            if "k_sc" in cache:   # int8 KV: per-(token,head) scales
                new["k_sc"], new["v_sc"] = apply_layer_head_perms(
                    cache["k_sc"], cache["v_sc"], rel,
                    layer_axis=0, head_axis=-1, group_size=G, rep=rep)
            return dict(state, cache=new), True, None
        if rel.shape[0] == 1 or bool(np.all(rel == rel[0])):
            # one layout for every layer: global permutation broadcasts
            # over any leading stack axes (dense AND VLM (G,4,...))
            if G > 1:
                kv_rows = kv_group_perms(rel[:1], G)
                if rep > 1:
                    kv_rows = expand_kv_perms(kv_rows, rep)
                rkv = jnp.asarray(kv_rows[0])
            else:
                rkv = jnp.asarray(rel[0])
            if permute_params:
                self.params = permute_model_heads(self.params, rel[0],
                                                  group_size=G)
            new["k"] = jnp.take(cache["k"], rkv, axis=-2)
            new["v"] = jnp.take(cache["v"], rkv, axis=-2)
            if "k_sc" in cache:
                new["k_sc"] = jnp.take(cache["k_sc"], rkv, axis=-1)
                new["v_sc"] = jnp.take(cache["v_sc"], rkv, axis=-1)
            out = dict(state, cache=new)
            if "img_kv" in state:
                # VLM static image K/V follow their (permuted) cross-attn
                # projections
                img = state["img_kv"]
                out["img_kv"] = dict(img,
                                     k=jnp.take(img["k"], rkv, axis=-2),
                                     v=jnp.take(img["v"], rkv, axis=-2))
            return out, True, None
        # per-layer plan on a cache layout we cannot address per layer
        return state, False, \
            "per-layer plan on a cache without a leading layer axis"

    def _feed_expert_loads(self, states: Sequence[Dict[str, Any]]):
        """Average the decode states' router-load EWMAs ((L, E) routed-token
        fractions), normalize rows to sum 1, and hand them to the
        controller's expert cost model — the live-load feedback edge of the
        expert block graph.  No-op for expert-oblivious cost models."""
        if not self.cost.n_experts:
            return
        loads = [np.asarray(st["expert_load"]) for st in states
                 if isinstance(st, dict) and "expert_load" in st]
        if not loads:
            return
        rows = np.mean(loads, axis=0)
        rows = rows / np.maximum(rows.sum(axis=-1, keepdims=True), 1e-9)
        self.controller.update_expert_loads(rows)

    def _migrate_experts(self, plan) -> tuple:
        """Execute the plan's expert migrations physically: permute the
        w_gate/w_up/w_down expert rows (and the owner/share maps that ride
        with them) by the per-layer relative permutations — weight-only,
        exactly as head migrations permute cache rows.  Params are shared
        across decode states, so this runs ONCE per plan.  Returns
        (applied, reason)."""
        if plan.get("prev_expert_perms") is None \
                or not plan.get("expert_migrations"):
            return False, None
        moe = self.params.get("layers", {})
        if not (isinstance(moe, dict) and "moe" in moe
                and "owner" in moe["moe"]):
            return False, "params carry no physical expert rows"
        from repro.core.placement_bridge import (
            permute_model_experts_layers, relative_perms)
        rel = relative_perms(plan["prev_expert_perms"], plan["expert_perms"])
        L = int(moe["moe"]["owner"].shape[0])
        if rel.shape[0] == 1:
            rel = np.broadcast_to(rel, (L, rel.shape[1]))
        if rel.shape[0] != L:
            return False, ("expert plan rows do not match the stacked "
                           "expert weights")
        self.params = permute_model_experts_layers(self.params, rel)
        return True, None

    def _interval(self, state, tau_tokens: Optional[int] = None):
        """The paper's controller interval: observe -> Algorithm 1 ->
        migrate head shards (and expert weight rows) in the decode gap."""
        self._feed_expert_loads([state])
        plan = self._interval_plan(tau_tokens)
        applied, reason = False, None
        if plan["migrations"]:
            state, applied, reason = self._migrate_state(state, plan)
        e_applied, e_reason = self._migrate_experts(plan)
        self._log_interval(plan, applied, reason, e_applied, e_reason)
        return state

    # ------------------------------------------------- migration pricing
    def _live_cache_tokens(self) -> int:
        """KV tokens a migration actually moves, summed over slots: dense
        engines hold (and must copy) the full reserved
        ``n_slots × max_seq`` extent per kv row.  The paged engine
        overrides this with its allocated page count — the measurable
        difference behind pages-as-the-migration-unit."""
        return self.n_slots * self.max_seq

    def _migration_bytes(self, pairs) -> int:
        """Bytes the plan's head migrations move through the cache: one
        k+v row over the live token extent per distinct migrated
        (layer, kv group) — ×rep replicas, +f32 scales for int8 KV."""
        hd = getattr(self.model, "hd", None)
        if hd is None or not hd.Hp or not pairs:
            return 0
        G = hd.Hp // hd.Kp if hd.Kp else 1
        kv_moves = {(l, h // G) for (l, h, _s, _d) in pairs}
        tokens = self._live_cache_tokens()
        if self.cfg.kv_quant:
            per_row = tokens * 2 * (hd.dh + 4)   # int8 k+v + f32 scales
        else:
            per_row = tokens * 2 * hd.dh * \
                jnp.dtype(self.cfg.dtype).itemsize
        return int(len(kv_moves) * hd.rep * per_row)

    def _expert_migration_bytes(self, pairs) -> int:
        """Bytes the plan's expert migrations move: 3·D·F weights per
        distinct migrated (layer, expert row) — weight-only, no KV term
        (Table I's expert column; the paper's m_i for experts)."""
        if not pairs:
            return 0
        moves = {(l, e) for (l, e, _s, _d) in pairs}
        D = self.cfg.d_model
        F = self.cfg.d_ff or 4 * D
        per = 3 * D * F * jnp.dtype(self.cfg.param_dtype).itemsize
        return int(len(moves) * per)

    def _log_interval(self, plan, applied: bool, reason: Optional[str],
                      expert_applied: bool = False,
                      expert_reason: Optional[str] = None):
        epairs = plan.get("expert_migrations") or []
        self.migration_log.append({
            "step": self.decode_steps,
            "arrival_rate": plan.get("arrival_rate"),
            "queue_depth": plan.get("queue_depth"),
            "n_migrations": len(plan["migrations"]),
            "mig_bytes": self._migration_bytes(plan["migrations"]),
            "n_expert_migrations": len(epairs),
            "expert_mig_bytes": self._expert_migration_bytes(epairs),
            "d_mig_est": plan["d_mig_est"],
            "d_pipe_est": plan.get("d_pipe_est"),
            "infeasible": plan.get("infeasible"),
            "plan_s": plan.get("plan_s"),
            "applied": applied, "reason": reason,
            "expert_applied": expert_applied,
            "expert_reason": expert_reason})


class ServingEngine(_EngineBase):
    """Continuous-batching scheduler: persistent per-slot KV cache, admit-
    on-free-slot, bucketed prefill, per-slot decode masking.

    ``pipeline_k`` > 1 keeps K decode tokens in flight across slot groups
    (micro-batched decode pipelining, Model-Distributed Inference style):
    the slots are partitioned into K contiguous groups with independent
    decode states, and each scheduler step advances ONE group — while
    group g's token transits the later layer stages, groups g+1..K-1 issue
    theirs into the earlier stages.  In-flight depth is bounded by slot
    occupancy (an empty group is a pipeline bubble, it cannot carry a
    token), and the controller's migration cadence scales by K: a slot
    generates one token every K steps, so λ tokens per slot = λ·K
    scheduler steps (the interval accounting stays token-denominated).

    VLM configs are slot-wired: ``submit`` takes per-request image patch
    embeddings, prefill projects them into the request's static image K/V,
    and ``insert_slot`` splices img_kv/img_mask rows alongside the cache.

    ``paged=True`` swaps the dense per-slot cache for the paged KV
    subsystem (serving.paging): a pooled page store per decode group, a
    per-slot page table, pages allocated as decode advances and freed on
    retire, and CHUNKED prefill through one fixed-shape jit (the bucket
    ladder disappears — ``prefill_chunk`` tokens per chunk, traced
    row/start/length).  ``kv_pages`` bounds the pool (the per-device
    memory budget knob: a smaller pool admits the same slots because
    they only hold live pages); migrations move only live pages and the
    controller prices cache memory page-granularly
    (``CostModel.page_size``).
    """

    def __init__(self, cfg: ModelConfig, *,
                 buckets: Optional[Sequence[int]] = None,
                 img_tokens: int = 16, paged: bool = False,
                 page_size: int = 64, kv_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None, **kw):
        # cheap cfg-only check BEFORE params/controller are built; the
        # served extent decides whether a sliding-window arch stays linear
        reason = supports_continuous(cfg, kw.get("max_seq", 512))
        if reason is not None:
            raise UnsupportedArchError(reason + "; use WaveServingEngine")
        self.paged = bool(paged)
        if self.paged:
            if cfg.family == "vlm":
                raise UnsupportedArchError(
                    "paged KV does not yet carry the VLM image K/V; "
                    "use paged=False")
            # the controller prices cache memory (and so migration bytes)
            # at page granularity — what the allocator actually hands out
            kw.setdefault("cost_page_size", page_size)
        super().__init__(cfg, **kw)
        assert hasattr(self.model, "prefill_bucketed"), type(self.model)
        if self.n_slots % self.pipeline_k:
            raise ValueError(f"n_slots={self.n_slots} must be divisible by "
                             f"pipeline_k={self.pipeline_k}")
        if self.pipeline_k > 1 and not self.greedy:
            raise ValueError("pipeline_k > 1 requires greedy decoding "
                             "(host-side sampling would serialize groups)")
        self.rows_per_group = self.n_slots // self.pipeline_k
        self.buckets = sorted(set(buckets)) if buckets \
            else default_buckets(self.max_seq)
        self.is_vlm = cfg.family == "vlm"
        self.img_tokens = img_tokens
        if self.paged:
            if self.max_seq % page_size:
                raise ValueError(f"max_seq={self.max_seq} must be a "
                                 f"multiple of page_size={page_size}")
            from repro.serving.paging import PagedKVAllocator
            self.page_size = int(page_size)
            self.pages_per_slot = self.max_seq // self.page_size
            # pool size per decode group: default = full dense reservation
            # (paged is then a pure refactor); a SMALLER pool is the
            # memory-budget knob — the same device bytes admit more slots
            # because slots only hold their live pages
            self.kv_pages = int(kv_pages) if kv_pages is not None \
                else self.rows_per_group * self.pages_per_slot
            self.allocators = [
                PagedKVAllocator(self.kv_pages, self.page_size,
                                 self.rows_per_group, self.pages_per_slot)
                for _ in range(self.pipeline_k)]
            # one fixed chunk shape = ONE prefill lowering, period
            self.prefill_chunk = int(prefill_chunk or self.page_size)
        # kernelized decode: per-layer gather maps (physical q-head rows in
        # slot-grouped placement order) threaded through the decode state.
        # VLM caches are (G, 4, ...) stacks migrated all-layers-equal, so
        # the identity maps the model defaults to stay correct there.
        self._rows_layers = 0
        if self.use_kernel and not self.is_vlm:
            hd = self.model.hd
            width = self.net.n_devices * self.controller.cfg.heads_per_slot
            if width != hd.Hp:
                raise UnsupportedArchError(
                    f"use_kernel: the bridge's {self.net.n_devices}x"
                    f"{self.controller.cfg.heads_per_slot} head-position "
                    f"space must equal the model's {hd.Hp} padded heads "
                    f"for placement-derived kernel grids")
            from repro.core.placement_bridge import identity_head_rows
            self._rows_layers = cfg.n_layers
            self._head_rows, self._head_inv = identity_head_rows(
                self._rows_layers, hd.Hp)
            self._phys_perms = None   # layout actually applied to weights
        self.states: List[Dict[str, Any]] = [
            self._attach_head_rows(self._fresh_state(self.rows_per_group))
            for _ in range(self.pipeline_k)]
        self.slots: List[Optional[Request]] = [None] * self.n_slots
        self._next = np.zeros(self.n_slots, np.int32)
        # donate like _decode_jit: the bucketed sub-state and the spliced
        # slot state are dead after each call (reassigned in _admit)
        self._prefill_bucketed_jit = jax.jit(self.model.prefill_bucketed,
                                             donate_argnums=(1,))
        self._insert_jit = jax.jit(self.model.insert_slot,
                                   donate_argnums=(0,))
        if self.paged:
            # chunked prefill + page-table mount: row/start/length are
            # traced scalars, so each is ONE lowering for all slots,
            # chunks, and prompt lengths (the HLO audit gates this)
            self._paged_prefill_jit = jax.jit(self.model.prefill_paged,
                                              donate_argnums=(1,))
            self._mount_jit = jax.jit(self.model.mount_slot_pages,
                                      donate_argnums=(0,))
        # observability: scheduler decisions + compile boundedness (bounded,
        # like sample_key_log: a serving loop must not grow per request)
        self.admission_log: Deque[dict] = \
            collections.deque(maxlen=4096)    # {step, slot, rid, bucket}
        self.prefill_buckets_used: set = set()
        self.slot_busy_steps = 0              # sum of active slots per step
        # paged counters: chunk dispatches, page-table mounts, and Σ live /
        # Σ pooled pages over the allocators at each decode dispatch (their
        # ratio is the mean share of the pool that holds tokens)
        self.prefill_chunks = 0
        self.page_mounts = 0
        self.live_page_steps = 0
        self.pool_page_steps = 0
        # elastic churn: recovery events (fail/rejoin) with their replay
        # accounting, plus the client-visible tokens dropped by recovery
        # (teacher-forced replay re-derives every stream, so this stays 0
        # unless a future recovery path chooses to shed work)
        self.recovery_log: List[dict] = []
        self.tokens_lost = 0
        self._replan_pending = False

    def _fresh_state(self, batch: int, max_seq: Optional[int] = None,
                     img: Optional[np.ndarray] = None,
                     img_mask: Optional[np.ndarray] = None):
        if self.paged:
            return self.model.init_paged_state(
                self.params, batch, self.kv_pages, self.page_size,
                self.pages_per_slot)
        kw: Dict[str, Any] = {"per_slot": True}
        if self.is_vlm:
            # fixed-size image K/V buffer; empty rows are fully masked and
            # project zero K/V, so imageless slots attend to nothing
            kw["img_embeds"] = jnp.zeros(
                (batch, self.img_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype)) if img is None \
                else jnp.asarray(img)
            kw["img_mask"] = jnp.zeros((batch, self.img_tokens), jnp.bool_) \
                if img_mask is None else jnp.asarray(img_mask)
        return self.model.init_decode_state(
            self.params, batch, max_seq or self.max_seq, **kw)

    # ----------------------------------------------------- kernel row maps
    def _attach_head_rows(self, state: Dict[str, Any]) -> Dict[str, Any]:
        if not self._rows_layers:
            return state
        return dict(state, head_rows=jnp.asarray(self._head_rows),
                    head_inv=jnp.asarray(self._head_inv))

    def _refresh_head_rows(self, plan: dict):
        """Rebuild the kernel gather maps from the controller's plan: the
        resident slices come from the BlockGraph placement
        (``placement_to_head_slices`` via ``head_row_maps``) mapped
        through the physical layout actually applied to weights/caches —
        after a migration the maps MUST be rebuilt or the grid would
        dispatch stale rows.  Row maps are data (same shape every
        interval), so no decode recompile happens."""
        if not self._rows_layers:
            return
        from repro.core.placement_bridge import head_row_maps
        self._head_rows, self._head_inv = head_row_maps(
            plan["place"], self.controller.blocks, self.net.n_devices,
            self.model.hd.Hp, perms=self._phys_perms)
        if self._rows_layers != self._head_rows.shape[0]:
            # columns-mode controller: one row for every model layer
            self._head_rows = np.broadcast_to(
                self._head_rows[0], (self._rows_layers,
                                     self._head_rows.shape[1])).copy()
            self._head_inv = np.broadcast_to(
                self._head_inv[0], self._head_rows.shape).copy()
        self.states = [self._attach_head_rows(st) for st in self.states]

    # ------------------------------------------------------------- geometry
    @property
    def state(self) -> Dict[str, Any]:
        """The decode state (single-group engines only — pipelined engines
        hold one state per in-flight group in ``states``)."""
        assert self.pipeline_k == 1, "pipelined engine: use .states[g]"
        return self.states[0]

    def _group_of(self, slot: int) -> tuple:
        return slot // self.rows_per_group, slot % self.rows_per_group

    # ---------------------------------------------------------------- intake
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               img_embeds: Optional[np.ndarray] = None) -> int:
        """``img_embeds`` (I, d_model), I <= ``img_tokens``: VLM image
        patch embeddings for this request (right-padded + masked into the
        engine's fixed image buffer).  Rejected at intake, not mid-run."""
        self._bucket(len(np.asarray(prompt)))   # reject over-long at intake
        if img_embeds is not None and not self.is_vlm:
            raise ValueError(f"{self.cfg.name} is not a VLM: it takes no "
                             f"image embeddings")
        rid = super().submit(prompt, max_new_tokens)
        if self.is_vlm:
            req = self.queue[-1]
            img = np.zeros((self.img_tokens, self.cfg.d_model), np.float32)
            mask = np.zeros((self.img_tokens,), bool)
            if img_embeds is not None:
                img_embeds = np.asarray(img_embeds)
                n = img_embeds.shape[0]
                if img_embeds.ndim != 2 or n > self.img_tokens \
                        or img_embeds.shape[1] != self.cfg.d_model:
                    raise ValueError(
                        f"img_embeds must be (I<={self.img_tokens}, "
                        f"{self.cfg.d_model}), got {img_embeds.shape}")
                img[:n] = img_embeds
                mask[:n] = True
            req.img, req.img_mask = img, mask
        return rid

    # ------------------------------------------------------------- scheduler
    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"prompt length {n} exceeds max bucket "
                         f"{self.buckets[-1]}")

    def _retire(self, slot: int):
        r = self.slots[slot]
        r.done = True
        r.t_done = time.monotonic()
        self.finished.append(r)
        self.slots[slot] = None
        self._next[slot] = 0
        if self.paged:
            # free the slot's pages and unmount its table row: the row's
            # future (clamped) writes drop and its reads are masked, so
            # recycled pages cannot be corrupted by a retired slot
            g, row = self._group_of(slot)
            self.allocators[g].release(row)
            self._mount(g, row, 0)
        self._emit_done(r)

    def _mount(self, g: int, row: int, pos: int):
        """Write slot ``row``'s page-table row from group ``g``'s
        allocator (and its position) into the group's decode state."""
        self.page_mounts += 1
        with self.spans.span("kv.mount", arg=row):
            self.states[g] = self._mount_jit(
                self.states[g], jnp.int32(row),
                jnp.asarray(self.allocators[g].page_map_row(row)),
                jnp.int32(pos))

    def _finish_check(self, slot: int):
        r = self.slots[slot]
        if (len(r.out_tokens) >= r.max_new_tokens
                or len(r.prompt) + len(r.out_tokens) >= self.max_seq - 1):
            self._retire(slot)

    def _admit(self):
        """Fill every free slot from the queue (FIFO, any prompt length).
        Loops until no slot is free — a request that retires at admission
        (1-token budget) frees its slot for the next queued request."""
        while self.queue:
            s = next((i for i in range(self.n_slots)
                      if self.slots[i] is None), None)
            if s is None:
                return
            if self.paged:
                if not self._admit_paged(s):
                    return      # head-of-line: wait for pages to free
                continue
            r = self.queue.pop(0)
            r.t_admit = time.monotonic()
            L0 = len(r.prompt)
            Lb = self._bucket(L0)
            toks = np.zeros((1, Lb), np.int32)
            toks[0, :L0] = r.prompt
            sub = self._fresh_state(
                1, Lb,
                img=None if r.img is None else r.img[None],
                img_mask=None if r.img_mask is None else r.img_mask[None])
            logits, sub = self._prefill_bucketed_jit(
                self.params, sub, jnp.asarray(toks),
                jnp.asarray([L0], jnp.int32))
            self.prefill_buckets_used.add(Lb)
            g, row = self._group_of(s)
            self.states[g] = self._insert_jit(self.states[g], sub, row)
            r.t_first = time.monotonic()
            self.slots[s] = r
            # rpr: ignore[RPR004] -- the admission-time sample IS the
            # scheduler's sync point: the first token must reach the host
            # to seed _next before the slot can decode
            tok = int(self._sample(logits)[0])
            self._next[s] = tok
            self._emit_token(r, tok)
            self.admission_log.append({"step": self.decode_steps, "slot": s,
                                       "rid": r.rid, "bucket": Lb})
            self._finish_check(s)

    def _admit_paged(self, s: int) -> bool:
        """Admit the queue head into free slot ``s``: reserve its
        worst-case page footprint (prompt + its own decode budget — so
        decode-time extension can never exhaust the pool mid-stream),
        allocate the prompt's pages, mount the table row, and run the
        prompt through the SINGLE fixed-shape chunked-prefill jit.
        Returns False when the pool cannot reserve yet (head-of-line
        wait: the request admits once running slots retire)."""
        r = self.queue[0]
        L0 = len(r.prompt)
        g, row = self._group_of(s)
        alloc = self.allocators[g]
        horizon = min(L0 + r.max_new_tokens + 1, self.max_seq)
        if not alloc.can_admit(L0, horizon):
            return False
        self.queue.pop(0)
        r.t_admit = time.monotonic()
        span = self.spans.span
        with span("sched.admit", rid=r.rid, arg=r.t_admit - r.t_submit,
                  t0=r.t_admit):
            pages = alloc.admit(row, n_tokens=L0, horizon=horizon)
            self._mount(g, row, 0)
            C = self.prefill_chunk
            logits = None
            for c0 in range(0, max(L0, 1), C):
                n = min(C, L0 - c0)
                toks = np.zeros((1, C), np.int32)
                toks[0, :n] = r.prompt[c0:c0 + n]
                with span("model.prefill_chunk", rid=r.rid, arg=c0):
                    logits, self.states[g] = self._paged_prefill_jit(
                        self.params, self.states[g], jnp.asarray(toks),
                        jnp.int32(row), jnp.int32(c0), jnp.int32(n))
                self.prefill_chunks += 1
            self.prefill_buckets_used.add(C)
            # the host waits here for the prompt's last chunk
            with span("sched.first_token", rid=r.rid) as ft:
                r.t_first = ft.t0 if ft else time.monotonic()
                self.slots[s] = r
                # rpr: ignore[RPR004] -- the admission-time sample IS the
                # scheduler's sync point: the first token must reach the
                # host to seed _next before the slot can decode
                tok = int(self._sample(logits)[0])
            self._next[s] = tok
            self._emit_token(r, tok)
            self.admission_log.append({"step": self.decode_steps, "slot": s,
                                       "rid": r.rid, "bucket": C,
                                       "pages": len(pages)})
            self._finish_check(s)
        return True

    def _ensure_pages(self, g: int, active: List[int], lo: int):
        """Lazy page growth: before group ``g`` decodes, any slot whose
        next write position crosses into an unallocated page draws one
        from its admission reservation and remounts its table row —
        live bytes track actual depth, not the reservation."""
        alloc = self.allocators[g]
        for s in active:
            row = s - lo
            r = self.slots[s]
            write_pos = len(r.prompt) + len(r.out_tokens) - 1
            if write_pos >= alloc.pages_for(row) * self.page_size:
                alloc.extend(row, write_pos + 1)
                self._mount(g, row, write_pos)

    def _live_cache_tokens(self) -> int:
        """Paged engines move only allocated pages (page-rounded live
        tokens, summed over groups) when a head's cache migrates."""
        if not self.paged:
            return super()._live_cache_tokens()
        return sum(a.live_pages for a in self.allocators) * self.page_size

    def _active(self) -> List[int]:
        return [s for s in range(self.n_slots) if self.slots[s] is not None]

    def _group_active(self, g: int) -> List[int]:
        lo = g * self.rows_per_group
        return [s for s in range(lo, lo + self.rows_per_group)
                if self.slots[s] is not None]

    def _occupancy(self) -> float:
        """Mean tokens resident per active slot (prompt + generated).
        Paged engines report page-rounded ALLOCATED tokens — the τ anchor
        then prices exactly the memory the allocator handed out."""
        act = self._active()
        if not act:
            return 0.0
        if self.paged:
            return float(np.mean(
                [self.allocators[self._group_of(s)[0]].pages_for(
                    self._group_of(s)[1]) * self.page_size for s in act]))
        return float(np.mean([len(self.slots[s].prompt)
                              + len(self.slots[s].out_tokens) for s in act]))

    def step(self) -> bool:
        """One scheduler iteration: admit into free slots, then one decode
        step for the in-flight group whose pipeline phase is due (with
        ``pipeline_k=1`` that is every active slot — the sequential path,
        unchanged).  Returns False when idle.

        An empty due group is a pipeline bubble: the step still advances
        the phase clock (in-flight depth is bounded by slot occupancy) but
        produces no tokens.

        The step is the root span (``sched.step``): the admission, decode,
        sampling, emit, controller and migration spans nest inside it."""
        with self.spans.root("sched.step", arg=self.decode_steps):
            return self._step()

    def _step(self) -> bool:
        span = self.spans.span
        self._admit()
        if not self._active():
            return False
        g = self.decode_steps % self.pipeline_k
        lo = g * self.rows_per_group
        active = self._group_active(g)
        if active:
            if self.paged:
                self._ensure_pages(g, active, lo)
                self.live_page_steps += sum(a.live_pages
                                            for a in self.allocators)
                self.pool_page_steps += sum(a.n_pages
                                            for a in self.allocators)
            with span("model.decode_dispatch") as sp:
                t0 = sp.t0 if sp else time.monotonic()
                nxt = self._next[lo:lo + self.rows_per_group]
                logits, self.states[g] = self._decode_jit(
                    self.params, self.states[g], jnp.asarray(nxt))
            with span("model.decode_wait") as sp:
                jax.block_until_ready(logits)
            dt = (sp.t1 if sp else time.monotonic()) - t0
            with span("sched.sample"):
                toks = self._sample(logits)
        self.decode_steps += 1
        if active:
            self.slot_busy_steps += len(active)
            with span("sched.emit"):
                for s in active:
                    # rpr: ignore[RPR004] -- post-block_until_ready host
                    # read: the scheduler needs concrete tokens for
                    # retire/admit
                    tok = int(toks[s - lo])
                    self._emit_token(self.slots[s], tok)
                    self._next[s] = tok
                    self._finish_check(s)
            self._record_step(dt)
        # migration cadence scales with the in-flight depth: a slot emits
        # one token every pipeline_k steps, so λ tokens per slot = λ·K
        # scheduler steps — the controller fires per λ *generated* tokens,
        # matching wall-clock token output (the τ anchor itself is already
        # token-denominated via _occupancy)
        if self._replan_pending \
                or self.decode_steps % (self.lam * self.pipeline_k) == 0:
            self._replan_pending = False
            # live router loads first: this interval's expert placement is
            # priced by the decode stream's gate frequencies, not the prior
            self._feed_expert_loads(self.states)
            plan = self._interval_plan(tau_tokens=self._occupancy())
            self._apply_plan(plan)
        return True

    def _apply_plan(self, plan: dict):
        """Execute a controller plan physically on every in-flight group:
        cache/weight permutations (weights once), expert weight rows once,
        kernel gather maps, interval log.  Shared by the periodic interval
        and the churn paths (failure evacuation / rejoin expansion).

        With spans on, ``mig.apply`` closes only once the permuted states
        and weights are ready, so it times the stall the permute causes
        (the next decode waits for them anyway)."""
        span = self.spans.span
        with span("mig.apply", arg=len(plan["migrations"])) as sp:
            applied, reason = False, None
            if plan["migrations"]:
                for i in range(self.pipeline_k):
                    with span("mig.permute", arg=i):
                        self.states[i], applied, reason = \
                            self._migrate_state(self.states[i], plan,
                                                permute_params=(i == 0))
            if applied:
                # weights/caches now sit in the plan's layout; the kernel
                # gather maps must follow the same source of truth
                self._phys_perms = plan["perms"]
            # expert rows are weight-only state shared by all groups:
            # permute them exactly once per plan
            e_applied, e_reason = self._migrate_experts(plan)
            with span("mig.head_rows"):
                self._refresh_head_rows(plan)
            if sp is not None:
                jax.block_until_ready((self.states, self.params))
        self._log_interval(plan, applied, reason, e_applied, e_reason)

    def run(self, max_steps: int = 10_000):
        while self.decode_steps < max_steps:
            if not self.step():
                break
        return self.finished

    # ------------------------------------------------------------- churn
    def request_replan(self):
        """Force the controller interval to fire on the next scheduler
        step regardless of the λ cadence — the async watchdog's recovery
        escalation hook (a hang must not wait out a long interval)."""
        self._replan_pending = True

    def slow_device(self, device: int, factor: float):
        """Persistent ``factor``x slowdown on ``device``: pinned load the
        monitor-fed observation surfaces at the next interval, where
        Algorithm 1 migrates away iff the move pays (§III.G)."""
        self.net.slow(device, factor)

    def fail_device(self, device: int) -> dict:
        """Device death mid-decode: evacuate, then recover bit-identically.

        The controller's evacuation plan moves the dead device's blocks to
        survivors (raising when they cannot hold them), and ``_apply_plan``
        permutes weights/caches into the new layout.  Head permutations
        always route rows *through* the dead device's cache rows, so part
        of every group's KV cache is unrecoverable — instead of shedding
        the affected requests, every in-flight stream is rebuilt by
        teacher-forced replay of its already-emitted tokens through the
        engine's own prefill/decode jits (identical ops, identical batch
        geometry => bitwise-identical cache, hence bit-identical surviving
        streams).  No client-visible token is dropped: ``tokens_lost``
        stays 0 and replay never re-emits or re-samples."""
        if not self.net.is_active(device):
            raise ValueError(f"device {device} is not active")
        self.monitor.mark_failed(device)
        self._feed_expert_loads(self.states)
        plan = self.controller.handle_failure(
            device, tau=self._tau_of(self._occupancy()))
        self._apply_plan(plan)
        stats = self._replay_groups()
        self.recovery_log.append({
            "step": self.decode_steps, "event": "fail",
            "device": int(device), "tokens_lost": 0,
            "d_mig_est": plan["d_mig_est"],
            "d_pipe_est": plan["d_pipe_est"], **stats})
        return plan

    def rejoin_device(self, device: int) -> dict:
        """A previously failed device returns (empty): the controller's
        expansion plan re-spreads blocks onto it when that pays, and
        ``_apply_plan`` executes the moves — migration copies KV rows
        from surviving sources, so rejoin needs no replay."""
        if self.net.is_active(device):
            raise ValueError(f"device {device} is already active")
        plan = self.controller.handle_rejoin(
            device, tau=self._tau_of(self._occupancy()))
        self.monitor.record_heartbeat(device)
        self._apply_plan(plan)
        self.recovery_log.append({
            "step": self.decode_steps, "event": "rejoin",
            "device": int(device),
            "n_migrations": len(plan["migrations"])})
        return plan

    # ----------------------------------------------------------- replay
    def _replay_groups(self) -> dict:
        stats = {"replay_steps": 0, "replay_prefills": 0,
                 "replayed_slots": 0}
        for g in range(self.pipeline_k):
            st = self._replay_group(g)
            for k in stats:
                stats[k] += st[k]
        return stats

    def _replay_group(self, g: int) -> dict:
        """Rebuild group ``g``'s KV cache from its slots' request records.

        Slots are re-prefilled and then teacher-forced through the SAME
        donated decode jit, in the same batch geometry, feeding each
        already-emitted token at the position that originally produced its
        successor.  Unequal depths are staggered: with n_s tokens emitted
        on slot s and N = max(n_s), slot s is inserted at tick N - n_s so
        every slot finishes together — before insertion its row decodes
        garbage exactly like a freed slot's row, which the masking tests
        prove cannot touch other rows.  Replay samples nothing and emits
        nothing: ``_next``/``sample_count``/``decode_steps`` are whatever
        live decode left them."""
        active = self._group_active(g)
        lo = g * self.rows_per_group
        if self.paged:
            # the old allocator's page map described the pre-failure cache;
            # a fresh pool re-admitted in slot order reproduces admission's
            # reservations against the rebuilt (empty) page buffer
            from repro.serving.paging import PagedKVAllocator
            self.allocators[g] = PagedKVAllocator(
                self.kv_pages, self.page_size, self.rows_per_group,
                self.pages_per_slot)
        self.states[g] = self._attach_head_rows(
            self._fresh_state(self.rows_per_group))
        out = {"replay_steps": 0, "replay_prefills": 0,
               "replayed_slots": len(active)}
        if not active:
            return out
        ns = {s: len(self.slots[s].out_tokens) for s in active}
        max_n = max(ns.values())
        for i in range(max_n):
            for s in active:
                if max_n - ns[s] == i:
                    self._replay_insert(g, s)
                    out["replay_prefills"] += 1
            if i == max_n - 1:
                break   # the last emitted token was never decoded upon
            nxt = np.zeros(self.rows_per_group, np.int32)
            for s in active:
                k = i - (max_n - ns[s])
                if k >= 0:
                    r = self.slots[s]
                    nxt[s - lo] = r.out_tokens[k]
                    if self.paged:
                        # this step writes position L0 + k for slot s
                        self._replay_extend(g, s - lo, len(r.prompt) + k)
            _, self.states[g] = self._decode_jit(
                self.params, self.states[g], jnp.asarray(nxt))
            out["replay_steps"] += 1
        return out

    def _replay_insert(self, g: int, s: int):
        """Re-run slot ``s``'s admission-time prefill (same jits, same
        chunking/bucketing) into the rebuilt group state."""
        r = self.slots[s]
        row = s - g * self.rows_per_group
        L0 = len(r.prompt)
        if self.paged:
            alloc = self.allocators[g]
            horizon = min(L0 + r.max_new_tokens + 1, self.max_seq)
            alloc.admit(row, n_tokens=L0, horizon=horizon)
            self._mount(g, row, 0)
            C = self.prefill_chunk
            for c0 in range(0, max(L0, 1), C):
                n = min(C, L0 - c0)
                toks = np.zeros((1, C), np.int32)
                toks[0, :n] = r.prompt[c0:c0 + n]
                _, self.states[g] = self._paged_prefill_jit(
                    self.params, self.states[g], jnp.asarray(toks),
                    jnp.int32(row), jnp.int32(c0), jnp.int32(n))
            return
        Lb = self._bucket(L0)
        toks = np.zeros((1, Lb), np.int32)
        toks[0, :L0] = r.prompt
        sub = self._fresh_state(
            1, Lb,
            img=None if r.img is None else r.img[None],
            img_mask=None if r.img_mask is None else r.img_mask[None])
        _, sub = self._prefill_bucketed_jit(
            self.params, sub, jnp.asarray(toks),
            jnp.asarray([L0], jnp.int32))
        self.states[g] = self._insert_jit(self.states[g], sub, row)

    def _replay_extend(self, g: int, row: int, write_pos: int):
        alloc = self.allocators[g]
        if write_pos >= alloc.pages_for(row) * self.page_size:
            alloc.extend(row, write_pos + 1)
            self._mount(g, row, write_pos)


class WaveServingEngine(_EngineBase):
    """The old wave-based static scheduler: equal-length prompts per wave,
    lock-step decode, slots freed only when the wave drains.  Kept as the
    baseline for ``benchmarks/serving_throughput.py``."""

    def _next_wave(self) -> List[Request]:
        """Up to n_slots queued requests with equal prompt length."""
        if not self.queue:
            return []
        L0 = len(self.queue[0].prompt)
        wave = [r for r in self.queue if len(r.prompt) == L0][:self.n_slots]
        for r in wave:
            self.queue.remove(r)
        return wave

    def _run_wave(self, wave: List[Request], max_steps: int):
        B = self.n_slots
        L0 = len(wave[0].prompt)
        prompts = np.zeros((B, L0), np.int32)
        for i, r in enumerate(wave):
            prompts[i] = r.prompt
        state = self.model.init_decode_state(self.params, B, self.max_seq)
        logits, state = self._prefill_jit(self.params, state,
                                          jnp.asarray(prompts))
        for r in wave:
            r.t_first = time.monotonic()
        active = {i: r for i, r in enumerate(wave)}
        nxt = self._sample(logits)
        while active and self.decode_steps < max_steps:
            for i, r in list(active.items()):
                # rpr: ignore[RPR004] -- wave scheduler's finish check
                # runs on host tokens; nxt is already device-synced
                self._emit_token(r, int(nxt[i]))
                if (len(r.out_tokens) >= r.max_new_tokens
                        or L0 + len(r.out_tokens) >= self.max_seq - 1):
                    r.done = True
                    r.t_done = time.monotonic()
                    self.finished.append(r)
                    del active[i]
                    self._emit_done(r)
            if not active:
                break
            t0 = time.monotonic()
            logits, state = self._decode_jit(self.params, state,
                                             jnp.asarray(nxt))
            jax.block_until_ready(logits)
            dt = time.monotonic() - t0
            nxt = self._sample(logits)
            self.decode_steps += 1
            self._record_step(dt)
            if self.decode_steps % self.lam == 0:
                state = self._interval(state)

    def run(self, max_steps: int = 10_000):
        while self.queue and self.decode_steps < max_steps:
            wave = self._next_wave()
            if not wave:
                break
            self._run_wave(wave, max_steps)
        return self.finished


def make_engine(cfg: ModelConfig, *, mode: str = "auto", **kw):
    """``continuous`` | ``wave`` | ``auto`` (continuous when the arch
    supports the slot API, wave otherwise)."""
    if mode == "wave":
        return WaveServingEngine(cfg, **kw)
    if mode == "continuous":
        return ServingEngine(cfg, **kw)
    try:
        return ServingEngine(cfg, **kw)
    except NotImplementedError:
        return WaveServingEngine(cfg, **kw)
