"""Interval controller — the paper's §III.G loop, host-side.

Unifies the two runtimes:
 - simulator: DeviceNetwork snapshots drive Algorithm 1 directly;
 - TPU serving: step-time telemetry (runtime.fault_tolerance) estimates
   C_j(τ), KV-cache growth gives m_i(τ), the ICI matrix gives R_{j,k};
   Algorithm 1's placement becomes a head permutation (placement_bridge)
   and the migration plan is applied to the cache between decode steps —
   in the λ-interval slack, exactly where the paper schedules migrations.

With a ``layer_mode="graph"`` cost model the controller places the full
per-layer block graph and emits **one head permutation per layer**
(``plan["perms"]``, shape (n_layers, n_slots·heads_per_slot)), so a
stacked KV cache is permuted layer-by-layer and head(l,i) can sit on a
different device than head(l',i).  ``plan["perm"]``/``plan["prev_perm"]``
remain the layer-0 rows for single-layer callers.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro.core.algorithm import ResourceAwareAssigner
from repro.core.blocks import Block, CostModel, make_blocks
from repro.core.delay import (migration_delay, pipelined_inference_delay,
                              revert_unpaying_migrations)
from repro.core.network import DeviceNetwork
from repro.core.placement_bridge import (apply_head_perm,
                                         apply_layer_head_perms,
                                         migration_pairs_layers,
                                         placement_to_expert_perms,
                                         placement_to_perms, relative_perms)
from repro.runtime.spans import SpanRecorder


@dataclasses.dataclass
class ControllerConfig:
    lam: int = 32                 # tokens per interval (λ)
    deadline: float = 0.2         # per-token latency budget (scoring)
    min_gain: float = 0.0         # extra migration-filter margin
    heads_per_slot: int = 2
    # KV-group size (GQA: Hp // KvE query heads per KV head).  > 1 makes
    # every emitted permutation group-consistent, so grouped caches/weights
    # can physically migrate (placement_bridge.kv_group_perms).
    group_size: int = 1
    # decode tokens in flight across layer-disjoint stages; > 1 switches
    # the migration-filter objective to D_pipe(K) + D_mig and the engine
    # scales its interval cadence by K (λ stays token-denominated while a
    # scheduler step advances only 1/K of the slots).
    pipeline_k: int = 1
    # placement search mode: "rescoring" is the PR-3 path (Algorithm 1,
    # refine, filter); "bottleneck" (with pipeline_k > 1) adds the
    # bottleneck-targeted search — stage-balanced chain seed + layer-chain
    # moves aimed at the argmax resource, migrations amortized over
    # ``amortize`` intervals (baselines.ResourceAwarePolicy docstring).
    search: str = "rescoring"
    amortize: int = 16
    # physical expert rows per mesh slot (MoE archs).  0 = derive from the
    # cost model: expert_slots // n_devices (expert rows, like heads, tile
    # the mesh).  Only consulted when the cost model carries experts.
    experts_per_slot: int = 0


class IntervalController:
    """Runs Algorithm 1 every λ generated tokens and emits migration plans."""

    def __init__(self, n_heads: int, cost: CostModel, net: DeviceNetwork,
                 cfg: ControllerConfig = ControllerConfig(),
                 spans: Optional[SpanRecorder] = None):
        self.n_layers = cost.n_layers if cost.layer_mode == "graph" else 1
        self.blocks: List[Block] = make_blocks(n_heads, self.n_layers,
                                               cost.n_experts,
                                               cost.expert_replicas)
        self.cost = cost
        self.net = net
        self.cfg = cfg
        self.has_experts = cost.n_experts >= 2
        self.experts_per_slot = cfg.experts_per_slot
        if self.has_experts and not self.experts_per_slot:
            self.experts_per_slot = max(1, cost.expert_slots // net.n_devices)
        # the feasibility budget is the WHOLE interval: λ tokens at the
        # per-token deadline (conflating them made every ffn infeasible)
        self.assigner = ResourceAwareAssigner(self.blocks, cost,
                                              deadline=cfg.deadline * cfg.lam)
        # bottleneck-targeted search mode: plans come from the full policy
        # (assign → refine → filter → bottleneck search) so the engine's
        # real migrations follow the steady-state objective; the default
        # "rescoring" path below stays bit-for-bit the PR-3 controller,
        # as does "bottleneck" at pipeline_k=1 (the search only exists on
        # the pipelined objective).  Unknown modes fail HERE, at
        # construction — a typo must not silently serve the rescoring
        # planner the caller opted out of.
        from repro.core.baselines import ResourceAwarePolicy
        if cfg.search not in ResourceAwarePolicy.SEARCH_MODES:
            raise ValueError(
                f"ControllerConfig.search must be one of "
                f"{ResourceAwarePolicy.SEARCH_MODES}, got {cfg.search!r}")
        self._policy = None
        if cfg.search == "bottleneck" and cfg.pipeline_k > 1:
            self._policy = ResourceAwarePolicy(
                self.blocks, cost, deadline=cfg.deadline * cfg.lam,
                pipeline_k=cfg.pipeline_k, search="bottleneck",
                amortize=cfg.amortize, min_gain=cfg.min_gain)
        self.place: Optional[np.ndarray] = None
        self.perms: Optional[np.ndarray] = None   # (n_layers, slots·hps)
        # (n_layers, slots·eps) physical expert-row layout (MoE archs)
        self.expert_perms: Optional[np.ndarray] = None
        self.tau = 0
        # ctl.assign / payback / perms / estimate spans (runtime.spans);
        # the serving engine passes its own recorder
        self.spans = spans if spans is not None else SpanRecorder()

    @property
    def perm(self) -> Optional[np.ndarray]:
        """Layer-0 permutation (single-layer backward compatibility)."""
        return None if self.perms is None else self.perms[0]

    def head_counts(self, place: Optional[np.ndarray] = None) -> np.ndarray:
        """Heads per device, summed over layers."""
        place = self.place if place is None else place
        heads = [b.index for b in self.blocks if b.kind == "head"]
        return np.bincount(np.asarray(place)[heads],
                           minlength=self.net.n_devices)

    # ------------------------------------------------------------ observe
    def observe(self, compute_avail: Optional[np.ndarray] = None,
                mem_avail: Optional[np.ndarray] = None):
        """Feed observed instantaneous availability.  ``mem_avail`` lands
        in the network's availability field — hardware ``mem_capacity`` is
        never overwritten by an observation (the old conflation made one
        low-memory sample permanently shrink the device)."""
        if compute_avail is not None:
            obs = np.asarray(compute_avail, float)
            # an inactive device has zero availability no matter what the
            # (possibly stale) telemetry claims
            self.net.compute_avail = np.where(self.net.active, obs, 0.0)
        if mem_avail is not None:
            self.net.mem_avail = np.asarray(mem_avail, float)

    def observe_monitor(self, monitor, peak_flops=None):
        """Close the fault_tolerance loop: per-slot step-time EWMAs from a
        ``HeartbeatMonitor`` become the C_j(τ) estimates Algorithm 1
        reads.  Slot j maps to device j (the engine's convention).  Dead
        slots estimate to zero; devices already failed in the network stay
        at zero regardless of telemetry."""
        peak = self.net.compute_max if peak_flops is None else peak_flops
        self.observe(compute_avail=monitor.availability(peak))

    def update_expert_loads(self, loads):
        """Feed observed router loads (rows: per layer, one entry per
        physical expert slot, each row summing to ~1) into the expert cost
        model.  The assigner/policy are rebuilt around the new CostModel so
        the *next* ``step_interval`` prices expert compute and placement by
        the live gate frequencies — the engine calls this each interval
        with the decode state's router-load EWMA."""
        if not self.has_experts:
            return
        self.cost = self.cost.with_expert_loads(loads)
        self.assigner = ResourceAwareAssigner(
            self.blocks, self.cost,
            deadline=self.cfg.deadline * self.cfg.lam)
        if self._policy is not None:
            from repro.core.baselines import ResourceAwarePolicy
            self._policy = ResourceAwarePolicy(
                self.blocks, self.cost,
                deadline=self.cfg.deadline * self.cfg.lam,
                pipeline_k=self.cfg.pipeline_k, search="bottleneck",
                amortize=self.cfg.amortize, min_gain=self.cfg.min_gain)

    # ------------------------------------------------------------- decide
    def step_interval(self, tau: Optional[int] = None,
                      arrival_rate: Optional[float] = None,
                      queue_depth: Optional[int] = None) -> dict:
        """One controller interval: assign, diff, plan migrations.

        ``tau`` lets the serving engine anchor the cost model to the
        *actual* decode stream — e.g. the mean KV-cache occupancy across
        continuous-batching slots (which sit at different depths) — instead
        of the lock-step +1-per-interval counter the simulator uses.

        ``arrival_rate`` (requests per scheduler step since the last
        interval) and ``queue_depth`` (backlog at the interval boundary)
        are the engine's observed LOAD — recorded into the plan so the
        controller's view covers the arrival process, not just resident
        occupancy.  Today they are telemetry; they are the
        input the traffic-adaptive search (ROADMAP) will act on."""
        self.tau = max(1, int(tau)) if tau is not None else self.tau + 1
        prev = self.place
        k = self.cfg.pipeline_k
        span = self.spans.span
        with span("ctl.assign"):
            if self._policy is not None:
                # bottleneck mode: the policy already refines, filters
                # (with min_gain) and runs the bottleneck-targeted search
                place = self._policy.place(self.net, self.tau, prev)
                stats = self._policy.last_stats
            else:
                place, stats = self.assigner.assign(self.net, self.tau, prev)
            if place is None:
                place = prev if prev is not None else \
                    np.zeros(len(self.blocks), dtype=int)
        if self._policy is None:
            # objective filter: keep migrations only if they pay (§III.G).
            # With pipeline_k > 1 the objective is D_pipe(K) + D_mig — a
            # move that lengthens the critical path but relieves the
            # bottleneck resource can now win (k=1 is total_delay
            # bit-for-bit).
            with span("ctl.payback"):
                place = revert_unpaying_migrations(
                    prev, place, self.blocks, self.cost, self.net, self.tau,
                    k=k, min_gain=self.cfg.min_gain)
        n_slots = self.net.n_devices
        with span("ctl.perms"):
            new_perms = placement_to_perms(place, self.blocks, n_slots,
                                           self.cfg.heads_per_slot,
                                           self.cfg.group_size)
            pairs = [] if self.perms is None else \
                migration_pairs_layers(self.perms, new_perms,
                                       self.cfg.heads_per_slot)
            new_eperms = None
            epairs: List[tuple] = []
            if self.has_experts:
                new_eperms = placement_to_expert_perms(
                    place, self.blocks, n_slots, self.experts_per_slot,
                    self.cost.expert_replicas)
                if self.expert_perms is not None:
                    epairs = migration_pairs_layers(
                        self.expert_perms, new_eperms, self.experts_per_slot)
        with span("ctl.estimate"):
            d_mig = migration_delay(prev, place, self.blocks, self.cost,
                                    self.net, self.tau)
            d_pipe = pipelined_inference_delay(place, self.blocks, self.cost,
                                               self.net, self.tau, k=k)
        plan = {"tau": self.tau, "place": place,
                "perms": new_perms, "prev_perms": self.perms,
                "perm": new_perms[0],
                "prev_perm": None if self.perms is None else self.perms[0],
                "migrations": pairs,
                "expert_perms": new_eperms,
                "prev_expert_perms": self.expert_perms,
                "expert_migrations": epairs,
                "d_mig_est": d_mig,
                "d_pipe_est": d_pipe,
                "arrival_rate": arrival_rate,
                "queue_depth": queue_depth,
                "infeasible": stats.infeasible,
                "assign_s": stats.elapsed}
        self.place, self.perms = place, new_perms
        if new_eperms is not None:
            self.expert_perms = new_eperms
        return plan

    # ------------------------------------------------------------- churn
    def handle_failure(self, device: int,
                       tau: Optional[int] = None) -> dict:
        """Death event → evacuation plan: mark ``device`` failed and
        immediately re-place.  The resulting plan's migrations move every
        block off the dead device (the assigner cannot place there), and
        the §III.G payback filter is structurally bypassed for them —
        ``revert_unpaying_migrations`` never reverts a block onto an
        inactive device — so the evacuation is mandatory, not priced.
        Surviving blocks keep their hysteresis stickiness, minimizing
        collateral migrations."""
        self.net.fail(device)
        plan = self.step_interval(tau=tau)
        if np.any(np.asarray(plan["place"]) == device):
            # the infeasible fallback kept blocks on the dead device —
            # survivors cannot hold the model; fail loudly, not silently
            raise RuntimeError(
                f"evacuation infeasible: surviving devices cannot hold "
                f"device {device}'s blocks (n_active={self.net.n_active})")
        plan["evacuation"] = True
        plan["failed_device"] = int(device)
        return plan

    def handle_rejoin(self, device: int,
                      tau: Optional[int] = None) -> dict:
        """A failed device comes back (fresh, no resident state) →
        expansion plan.  Unlike evacuation, expansion is optional: the
        controller only migrates onto the rejoined device when the move
        pays under the normal §III.G filter."""
        self.net.rejoin(device)
        plan = self.step_interval(tau=tau)
        plan["expansion"] = True
        plan["rejoined_device"] = int(device)
        return plan

    # ---------------------------------------------------------------- act
    def apply_to_cache(self, cache_k, cache_v, plan, head_axis: int = 3,
                       layer_axis: int = 0):
        """Execute the migration plan on a layer-stacked head-expanded KV
        cache: per-layer gathers by the *relative* permutations (new layout
        in terms of current positions), which lower to collective-permute
        between slots.  The cache's ``layer_axis`` must cover the
        controller's ``n_layers`` (a single-layer plan broadcasts over it)."""
        prev_perms = plan.get("prev_perms")
        if prev_perms is None or not plan["migrations"]:
            return cache_k, cache_v
        rel = relative_perms(prev_perms, plan["perms"])
        gs = self.cfg.group_size
        if rel.shape[0] == 1:  # single-layer plan: same perm for all layers
            return apply_head_perm(cache_k, cache_v, rel[0], head_axis,
                                   group_size=gs)
        return apply_layer_head_perms(cache_k, cache_v, rel,
                                      layer_axis=layer_axis,
                                      head_axis=head_axis, group_size=gs)
