"""Delay model — Eq. (2)–(7) of the paper, generalized to a per-layer
block graph.

A placement is an int array ``place[block_index] -> device``.

Single layer (Eq. 6, with the natural completion of the pipeline: proj and
ffn processing included — the paper's equation lists the communication
terms explicitly and §III.E(b) defines processing delays for *every*
block; ``strict_eq6=True`` reproduces the bare printed form):

  D_T = max_{i∈H}( D_in→d(i) + D_proc(i) + D_{d(i)→d(proj)} )
        [+ D_proc(proj)] + D_{d(proj)→d(ffn)} [+ D_proc(ffn)]

Multi-layer (``make_blocks(h, n_layers)`` graphs): one decode token
traverses the layers sequentially — there is no intra-token pipelining —
so the total is the layer-composed critical path

  D_T = Σ_l D_layer(l)

where D_layer(l) is Eq. 6 applied to layer l's blocks with layer l's input
stage replaced by the inter-layer edge: layer 0's heads receive the token
embeddings from the controller (``input_bytes``), layer l>0's heads
receive the previous layer's output from d(ffn(l-1))
(``interlayer_bytes``).  Because the layers execute back-to-back, every
directed link serializes all layers' transfers and every device runs all
layers' resident blocks sequentially — the cross-layer sharing shows up as
the Σ_l composition, and the intra-layer sharing as Eq. 6's per-link /
per-device sums.  With n_layers=1 the loop body is the original Eq. 6
arithmetic, bit-for-bit.

Concurrency semantics (§III.E/F), per layer:
 - compute: blocks co-located on a device run sequentially — a head's
   processing term uses the *sum* of that layer's head compute on its
   device;
 - links: transfers sharing a directed link (j,k) are serialized — each
   head's comm term uses the summed volume on its link.  The inter-layer
   broadcast is one transfer per destination device (co-located heads
   share it), matching the controller-input convention.

Migration (Eq. 2/7): D_mig = Σ_i m_i(τ-1)/R_{j,k}(τ), serialized per link
— unchanged: per-layer blocks each contribute their single-layer
footprint.

Pipelined decode (beyond the printed model; Model-Distributed Inference,
arXiv 2505.18164, and the comm/compute overlap accounting of arXiv
2211.05102): with per-layer placements, consecutive decode tokens of
*different* requests can occupy layer-disjoint device sets concurrently.
``pipelined_inference_delay`` models K in-flight tokens: the first token
pays the full sequential critical path D_T (pipeline fill), every further
token is admitted one steady-state interval B later, where B is the
busiest single resource's per-token busy time (per-device compute and
per-directed-link transfer serialization are preserved — a resource can
only serve one token's work at a time).  Per-token amortized delay:

  D_pipe(K) = (D_T + (K-1)·B) / K,   B = min(bottleneck, D_T)

K=1 is bit-for-bit ``inference_delay``.  B is clamped to D_T because Eq. 6's
max-over-heads form can under-serialize transfers in *different* head
chains sharing one directed link; operationally a pipeline can always
degrade to sequential issue, so the steady-state interval never exceeds
D_T — which also makes D_pipe(K) ≤ D_T an invariant for every K ≥ 1.
"""
from __future__ import annotations

import bisect
from typing import Optional, Sequence

import numpy as np

from repro.core.blocks import EXPERT, FFN, Block, CostModel, graph_of
from repro.core.network import DeviceNetwork


def _rate(net: DeviceNetwork, j: int, k: int) -> float:
    if j == k:
        return np.inf
    return float(net.bandwidth[j, k])


def _cdiv(x: float, rate: float) -> float:
    """Compute-time division pricing a dead device (C_j = 0) as +inf
    without tripping numpy's divide-by-zero warning: a placement that
    still references an inactive device has unbounded delay."""
    return float(x) / float(rate) if rate > 0.0 else np.inf


def _expert_stage(g, l, place, cost, tau):
    """Per-device (load fraction, summed compute) of layer l's expert
    blocks: the router fan-out/combine structure the delay model prices.

    Zero-load slots contribute nothing (no tokens are routed there); the
    per-device compute is summed BEFORE the single divide by the device
    rate so a co-located uniform-load expert set prices bit-for-bit like
    the dense ffn it collapses to."""
    agg: dict = {}
    for eb in g.experts[l]:
        ld = cost.expert_load(eb)
        if ld == 0.0:
            continue
        d = int(place[eb.index])
        fr, cp = agg.get(d, (0.0, 0.0))
        agg[d] = (fr + ld, cp + cost.compute(eb, tau))
    return agg


def _head_in(net: DeviceNetwork, sources, w_in: float, j: int) -> float:
    """Inbound time of a head on device j: each source's share of the
    activation over its link."""
    return sum(fr * w_in / _rate(net, s, j) for s, fr in sources)


def _head_chain(net: DeviceNetwork, j: int, n: int, on: float,
                t_in: float, d_proj: int, w_out: float) -> float:
    """Chain time of every head on device j, which holds n heads of summed
    compute ``on`` (co-located heads run sequentially): inbound + compute
    + the serialized head->proj volume (whole bytes, so count x volume is
    exact)."""
    return t_in + _cdiv(on, net.compute_avail[j]) + \
        n * w_out / _rate(net, j, d_proj)


def _worst(n_on: list, chain: list) -> float:
    """The head stage's time: the max chain over devices that host heads."""
    worst = 0.0
    for n, t in zip(n_on, chain):
        if n:
            worst = max(worst, t)
    return worst


def _layer_terms(g, l: int, place, sources, w_in: float, comp,
                 cost: CostModel, net: DeviceNetwork, tau: int,
                 strict_eq6: bool) -> tuple:
    """Layer l's additive D_T terms, in the order ``inference_delay`` sums
    them, the (device, load fraction) sources it hands layer l+1's heads,
    and its head stage: per device, the heads it holds and their chain
    time.  ``sources``/``w_in`` are what layer l's heads receive; ``comp``
    is ``cost.compute_vector(blocks, tau)``."""
    d_proj = int(place[g.proj[l].index])
    hidx = g.head_index[l]
    head_dev = place[hidx]
    # per-device head count and summed head compute (sequential sharing:
    # bincount adds in head order)
    n_on = np.bincount(head_dev, minlength=net.n_devices).tolist()
    on = np.bincount(head_dev, weights=comp[hidx],
                     minlength=net.n_devices).tolist()
    w_out = cost.head_to_proj_bytes(tau)
    chain = [_head_chain(net, j, n, on[j], _head_in(net, sources, w_in, j),
                         d_proj, w_out) if n else 0.0
             for j, n in enumerate(n_on)]
    heads = (n_on, chain)
    terms = [_worst(n_on, chain)]
    if not strict_eq6:
        terms.append(_cdiv(comp[g.proj[l].index], net.compute_avail[d_proj]))
    if g.ffn[l] is not None:
        d_ffn = int(place[g.ffn[l].index])
        terms.append(cost.proj_to_ffn_bytes(tau) / _rate(net, d_proj, d_ffn))
        if not strict_eq6:
            terms.append(_cdiv(comp[g.ffn[l].index],
                               net.compute_avail[d_ffn]))
        return terms, [(d_ffn, 1.0)], heads
    # expert stage: router fan-out (load-fraction-scaled proj->expert
    # transfer) + per-device expert compute, run in parallel across expert
    # devices -> the stage is the slowest device's (transfer, compute)
    # pair, added as two terms to keep the dense float association when
    # collapsed
    agg = _expert_stage(g, l, place, cost, tau)
    w_p2f = cost.proj_to_ffn_bytes(tau)
    stage_t = stage_c = 0.0
    stage = -1.0
    for d in sorted(agg):
        fr, cp = agg[d]
        t_x = fr * w_p2f / _rate(net, d_proj, d)
        t_c = 0.0 if strict_eq6 else _cdiv(cp, net.compute_avail[d])
        if t_x + t_c > stage:
            stage, stage_t, stage_c = t_x + t_c, t_x, t_c
    terms.append(stage_t)
    if not strict_eq6:
        terms.append(stage_c)
    return terms, [(d, agg[d][0]) for d in sorted(agg)], heads


def inference_delay(place: np.ndarray, blocks: Sequence[Block],
                    cost: CostModel, net: DeviceNetwork, tau: int,
                    *, strict_eq6: bool = False) -> float:
    """D_T(τ) for placement ``place``: Eq. 6 per layer, composed along the
    inter-layer edges (see module docstring)."""
    g = graph_of(blocks)
    comp = cost.compute_vector(g.blocks, tau)
    total = 0.0
    # layer 0: token embeddings from the controller; expert layers hand a
    # (device, load fraction) SOURCE LIST to the next layer's heads — the
    # router combine — which the dense path degenerates to as [(ffn, 1.0)]
    sources = [(net.controller, 1.0)]
    w_in = cost.input_bytes(tau)
    for l in range(g.n_layers):
        terms, sources, _ = _layer_terms(g, l, place, sources, w_in, comp,
                                         cost, net, tau, strict_eq6)
        for t in terms:
            total += t
        w_in = cost.interlayer_bytes(tau)
    return float(total)


def resource_busy_times(place: np.ndarray, blocks: Sequence[Block],
                        cost: CostModel, net: DeviceNetwork, tau: int,
                        *, strict_eq6: bool = False
                        ) -> tuple[np.ndarray, dict]:
    """Per-token busy time of every resource under ``place``: seconds each
    device computes and each directed link transfers for ONE token's
    traversal of all layers.  These are the §III.E serialization
    constraints expressed as steady-state pipeline occupancies: a stream of
    in-flight tokens cannot be admitted faster than the busiest resource
    drains one token's share.

    Returns ``(device_busy (V,), link_busy {(j, k): seconds})`` with
    same-device transfers omitted (rate ∞, zero busy either way).
    """
    g = graph_of(blocks)
    dev_busy = np.zeros(net.n_devices)
    link_busy: dict = {}

    def add_link(j: int, k: int, seconds: float):
        if j != k and seconds > 0.0:
            link_busy[(j, k)] = link_busy.get((j, k), 0.0) + seconds

    sources = [(net.controller, 1.0)]
    w_in = cost.input_bytes(tau)
    w_head = cost.head_to_proj_bytes(tau)
    for l in range(g.n_layers):
        heads = g.heads[l]
        d_proj = int(place[g.proj[l].index])
        head_devs = set()
        for h in heads:
            j = int(place[h.index])
            head_devs.add(j)
            dev_busy[j] += _cdiv(cost.compute(h, tau), net.compute_avail[j])
            add_link(j, d_proj, w_head / _rate(net, j, d_proj))
        # inter-layer broadcast: one transfer per destination device
        # (co-located heads share it — the controller-input convention);
        # expert layers fan in from every expert-hosting source device
        # with its load fraction's share of the activation
        for s, fr in sources:
            for j in sorted(head_devs):
                add_link(s, j, fr * w_in / _rate(net, s, j))
        if not strict_eq6:
            dev_busy[d_proj] += _cdiv(cost.compute(g.proj[l], tau),
                                      net.compute_avail[d_proj])
        if g.ffn[l] is not None:
            d_ffn = int(place[g.ffn[l].index])
            if not strict_eq6:
                dev_busy[d_ffn] += _cdiv(cost.compute(g.ffn[l], tau),
                                         net.compute_avail[d_ffn])
            add_link(d_proj, d_ffn,
                     cost.proj_to_ffn_bytes(tau) / _rate(net, d_proj, d_ffn))
            sources = [(d_ffn, 1.0)]
        else:
            agg = _expert_stage(g, l, place, cost, tau)
            w_p2f = cost.proj_to_ffn_bytes(tau)
            for d in sorted(agg):
                fr, cp = agg[d]
                if not strict_eq6:
                    dev_busy[d] += _cdiv(cp, net.compute_avail[d])
                add_link(d_proj, d, fr * w_p2f / _rate(net, d_proj, d))
            sources = [(d, agg[d][0]) for d in sorted(agg)]
        w_in = cost.interlayer_bytes(tau)
    return dev_busy, link_busy


def pipeline_bottleneck(place: np.ndarray, blocks: Sequence[Block],
                        cost: CostModel, net: DeviceNetwork, tau: int,
                        *, strict_eq6: bool = False) -> float:
    """Steady-state per-token interval of a fully pipelined decode stream:
    the busiest single resource's busy time (unclamped — callers comparing
    against D_T should use ``pipelined_inference_delay``)."""
    dev_busy, link_busy = resource_busy_times(place, blocks, cost, net, tau,
                                              strict_eq6=strict_eq6)
    worst = float(dev_busy.max()) if dev_busy.size else 0.0
    if link_busy:
        worst = max(worst, max(link_busy.values()))
    return worst


def bottleneck_attribution(place: np.ndarray, blocks: Sequence[Block],
                           cost: CostModel, net: DeviceNetwork, tau: int,
                           *, strict_eq6: bool = False) -> tuple:
    """WHICH resource is the pipeline bottleneck: the argmax of
    ``resource_busy_times``, i.e. the single device or directed link whose
    per-token busy time bounds the steady-state pipelined rate.

    Returns ``("device", j, seconds)`` or ``("link", (j, k), seconds)``
    with ``seconds == pipeline_bottleneck(...)``.  A bottleneck-targeted
    search relieves exactly this resource first — moving blocks that
    neither compute on it nor transfer over it cannot shrink B."""
    dev_busy, link_busy = resource_busy_times(place, blocks, cost, net, tau,
                                              strict_eq6=strict_eq6)
    kind: str = "device"
    ident: object = int(np.argmax(dev_busy)) if dev_busy.size else 0
    busy = float(dev_busy.max()) if dev_busy.size else 0.0
    for lk, seconds in link_busy.items():
        if seconds > busy:
            kind, ident, busy = "link", lk, float(seconds)
    return kind, ident, busy


def pipelined_inference_delay(place: np.ndarray, blocks: Sequence[Block],
                              cost: CostModel, net: DeviceNetwork, tau: int,
                              *, k: int = 1,
                              strict_eq6: bool = False) -> float:
    """Per-token D_T with ``k`` tokens in flight over layer-disjoint stages
    (module docstring): (D_T + (k-1)·B)/k with B = min(bottleneck, D_T).

    ``k=1`` returns ``inference_delay`` bit-for-bit; D_pipe(k) ≤ D_T for
    every k ≥ 1, with equality exactly when nothing overlaps (single
    device, or B == D_T)."""
    if k < 1:
        raise ValueError(f"pipeline depth k must be >= 1, got {k}")
    d_t = inference_delay(place, blocks, cost, net, tau,
                          strict_eq6=strict_eq6)
    if k == 1:
        return d_t
    b = min(pipeline_bottleneck(place, blocks, cost, net, tau,
                                strict_eq6=strict_eq6), d_t)
    return float((d_t + (k - 1) * b) / k)


def migration_delay(prev: Optional[np.ndarray], place: np.ndarray,
                    blocks: Sequence[Block], cost: CostModel,
                    net: DeviceNetwork, tau: int) -> float:
    """Eq. (7): serialized migrations, block footprint at τ-1 (Eq. 2).

    With ``CostModel.page_size`` set (paged serving), the head-block
    footprint rounds the live token extent up to page granularity, so
    the priced migration bytes track allocated pages — the same unit
    the engine physically transfers — instead of the worst-case
    ``max_seq`` reservation."""
    if prev is None:
        return 0.0
    total = 0.0
    for bl in blocks:
        j, k = int(prev[bl.index]), int(place[bl.index])
        if j != k:
            total += cost.memory(bl, tau - 1) / _rate(net, j, k)
    return float(total)


def total_delay(prev: Optional[np.ndarray], place: np.ndarray,
                blocks: Sequence[Block], cost: CostModel,
                net: DeviceNetwork, tau: int, *,
                strict_eq6: bool = False) -> float:
    return inference_delay(place, blocks, cost, net, tau,
                           strict_eq6=strict_eq6) + \
        migration_delay(prev, place, blocks, cost, net, tau)


class LayeredTotalDelay:
    """``total_delay(prev, place, ...)`` kept per layer, so that moving one
    block reprices only the layers it touches: its own, and the next one
    when the block feeds the next layer's heads (ffn / expert).

    The adopted terms are kept flat, in ``inference_delay``'s order, with
    their prefix sums, and so is the migration vector.  Pricing a candidate
    continues the adopted prefix before the first term it changes with the
    same sequential additions (``np.add.accumulate`` is strictly left to
    right; a migration entry of 0.0 adds nothing, so only moved blocks are
    summed), so every value equals ``total_delay`` bit for bit.  A head
    moves only its own layer's head-chain term: its proj / ffn terms and
    the sources it hands layer l+1 do not depend on where heads sit."""

    def __init__(self, prev: np.ndarray, blocks: Sequence[Block],
                 cost: CostModel, net: DeviceNetwork, tau: int):
        g = self.g = graph_of(blocks)
        self.cost, self.net, self.tau = cost, net, tau
        self.prev = np.asarray(prev, dtype=int)
        self.comp = cost.compute_vector(g.blocks, tau)
        self.mem_prev = cost.memory_vector(g.blocks, tau - 1)
        self.layer = np.array([b.layer for b in g.blocks])
        self.feeds_next = np.array([b.kind in (FFN, EXPERT)
                                    for b in g.blocks])
        # position of each head in its layer's ``head_index``; -1 otherwise
        self.head_pos = np.full(len(g.blocks), -1)
        for hidx in g.head_index:
            self.head_pos[hidx] = np.arange(len(hidx))
        self.w_out = cost.head_to_proj_bytes(tau)
        self.head_comp = [self.comp[hidx].tolist() for hidx in g.head_index]
        self.place = self.prev.copy()
        self.mig = np.zeros(len(g.blocks))   # prev == place: no moves
        self.out: list = [None] * g.n_layers
        # per layer: the adopted head stage (``_layer_terms``' third value)
        # and, once a head of the layer is priced, each device's t_in
        self.heads: list = [None] * g.n_layers
        self.head_in: list = [None] * g.n_layers
        terms = [self._reprice(l) for l in range(g.n_layers)]
        self.start = np.cumsum([0] + [len(t) for t in terms])
        self.flat = np.array([t for ts in terms for t in ts], dtype=float)
        self._prefix()

    def _sources(self, l: int):
        return [(self.net.controller, 1.0)] if l == 0 else self.out[l - 1]

    def _w_in(self, l: int) -> float:
        return self.cost.input_bytes(self.tau) if l == 0 \
            else self.cost.interlayer_bytes(self.tau)

    def _layer(self, l: int, place, sources):
        return _layer_terms(self.g, l, place, sources, self._w_in(l),
                            self.comp, self.cost, self.net, self.tau, False)

    def _reprice(self, l: int) -> list:
        """Reprice layer l under the adopted placement; returns its terms."""
        terms, self.out[l], self.heads[l] = self._layer(l, self.place,
                                                        self._sources(l))
        self.head_in[l] = None
        return terms

    def _prefix(self):
        # sums of the adopted terms before each position (leading 0.0),
        # and of the nonzero migration entries in block order
        self.pre = np.concatenate(([0.0], np.add.accumulate(self.flat)))
        at = np.flatnonzero(self.mig)
        self.mig_at = at.tolist()
        self.mig_val = self.mig[at]
        self.mig_pre = np.concatenate(([0.0],
                                       np.add.accumulate(self.mig_val)))

    def _mig_term(self, i: int, k: int) -> float:
        j = int(self.prev[i])
        return 0.0 if j == k else self.mem_prev[i] / _rate(self.net, j, k)

    def _touched(self, i: int) -> list:
        l = int(self.layer[i])
        return [l, l + 1] if self.feeds_next[i] and l + 1 < self.g.n_layers \
            else [l]

    def update(self, place: np.ndarray, moved: Optional[np.ndarray] = None):
        """Adopt ``place`` (complete, no -1), repricing only the layers
        whose blocks moved since the last adopted placement.  ``moved``,
        when given, holds every index that may differ (more is fine)."""
        if moved is None:
            moved = np.flatnonzero(place != self.place)
        else:
            moved = np.unique(moved)
            moved = moved[place[moved] != self.place[moved]]
        if not moved.size:
            return
        self.place[moved] = place[moved]
        dirty = set()
        for i in moved.tolist():
            self.mig[i] = self._mig_term(i, int(self.place[i]))
            dirty.update(self._touched(i))
        for l in sorted(dirty):      # ascending: layer l reads out[l - 1]
            self.flat[self.start[l]:self.start[l + 1]] = self._reprice(l)
        self._prefix()

    def _head_worsts(self, i: int, js: list) -> list:
        """Layer l's head-stage term (``_layer_terms``' first) with head i
        moved to each of ``js``.  Only the device it leaves and the one it
        joins change; their compute is re-summed in head order, as
        bincount does."""
        l, pos = int(self.layer[i]), int(self.head_pos[i])
        devs = self.place[self.g.head_index[l]].tolist()
        comp = self.head_comp[l]
        n_on, chain = self.heads[l]
        net = self.net
        if self.head_in[l] is None:
            sources, w_in = self._sources(l), self._w_in(l)
            self.head_in[l] = [_head_in(net, sources, w_in, j)
                               for j in range(net.n_devices)]
        t_in = self.head_in[l]
        d_proj = int(self.place[self.g.proj[l].index])
        a = devs[pos]
        # each device's head compute with head i joining it, and a's
        # without head i
        joined = [0.0] * len(n_on)
        left = 0.0
        for p, d in enumerate(devs):
            if p == pos:
                joined = [on + comp[p] for on in joined]
                continue
            joined[d] += comp[p]
            if d == a:
                left += comp[p]
        n, c = list(n_on), list(chain)
        n[a] -= 1
        c[a] = _head_chain(net, a, n[a], left, t_in[a], d_proj, self.w_out)
        out = []
        for j in js:
            n[j] += 1
            c_j, c[j] = c[j], _head_chain(net, j, n[j], joined[j], t_in[j],
                                          d_proj, self.w_out)
            out.append(_worst(n, c))
            n[j] -= 1
            c[j] = c_j
        return out

    def totals_with(self, i: int, js) -> list:
        """``total_delay`` of the adopted placement with block i on each
        device of ``js``, bit for bit."""
        total = self.total()                 # i stays where it is
        moves = [int(j) for j in js if j != self.place[i]]
        if not moves:
            return [total] * len(js)
        ls = self._touched(i)
        if self.head_pos[i] >= 0:
            new = [[t] for t in self._head_worsts(i, moves)]
        else:
            new = self._layers_with(i, moves, ls)
        a, n = int(self.start[ls[0]]), len(new[0])
        tail = self.flat[a + n:]
        # the migration vector with entry i replaced, its zeros skipped
        p = bisect.bisect_left(self.mig_at, i)
        q = p + (p < len(self.mig_at) and self.mig_at[p] == i)
        mig_tail = self.mig_val[q:]
        # one row per sum, both continued from their prefix in one
        # sequential pass (trailing zeros add nothing)
        K = len(moves)
        rows = np.zeros((2 * K, 2 + max(n + len(tail), len(mig_tail))))
        rows[:K, 0] = self.pre[a]
        rows[:K, 1:1 + n] = new
        rows[:K, 1 + n:1 + n + len(tail)] = tail
        rows[K:, 0] = self.mig_pre[p]
        rows[K:, 1] = [self._mig_term(i, j) for j in moves]
        rows[K:, 2:2 + len(mig_tail)] = mig_tail
        sums = np.add.accumulate(rows, axis=1)[:, -1]
        priced = iter((sums[:K] + sums[K:]).tolist())
        return [total if j == self.place[i] else next(priced) for j in js]

    def _layers_with(self, i: int, js: list, ls: list) -> list:
        """The terms of layers ``ls`` with block i on each of ``js``."""
        old = self.place[i]
        new = []
        try:
            for j in js:
                self.place[i] = j
                src, terms = self._sources(ls[0]), []
                for l in ls:
                    t, src, _ = self._layer(l, self.place, src)
                    terms += t
                new.append(terms)
        finally:
            self.place[i] = old
        return new

    def total_with(self, i: int, j: int) -> float:
        """``total_delay`` of the adopted placement with block i on j."""
        return self.totals_with(i, [j])[0]

    def total(self) -> float:
        """``total_delay`` of the adopted placement."""
        return float(self.pre[-1]) + float(self.mig_pre[-1])


def pipelined_total_delay(prev: Optional[np.ndarray], place: np.ndarray,
                          blocks: Sequence[Block], cost: CostModel,
                          net: DeviceNetwork, tau: int, *, k: int = 1,
                          strict_eq6: bool = False) -> float:
    """D_pipe(k) + D_mig — the objective pipeline-aware policies/solvers
    optimize.  ``k=1`` is ``total_delay`` bit-for-bit."""
    return pipelined_inference_delay(place, blocks, cost, net, tau, k=k,
                                     strict_eq6=strict_eq6) + \
        migration_delay(prev, place, blocks, cost, net, tau)


def revert_unpaying_migrations(prev: Optional[np.ndarray],
                               place: np.ndarray, blocks: Sequence[Block],
                               cost: CostModel, net: DeviceNetwork,
                               tau: int, *, k: int = 1,
                               min_gain: float = 0.0) -> np.ndarray:
    """§III.G's migration filter, shared by the controller and
    ``ResourceAwarePolicy``: each migrated block is reverted to its
    previous device when keeping the move does not lower
    D_pipe(k) + D_mig by at least ``min_gain`` (k=1: D_T + D_mig).
    Reverts are only taken when memory-feasible, and NEVER back onto an
    inactive device — an evacuation off a dead device is mandatory, so
    the §III.G payback filter cannot undo it (the bypass ISSUE/§III.G
    requires is structural, not a flag)."""
    if prev is None:
        return place
    current = place.copy()
    # memory is integral bytes, so the running per-device sums stay exact
    mem = cost.memory_vector(blocks, tau)
    use = memory_usage(current, blocks, cost, net, tau)
    usable = net.mem_usable() + 1e-9
    if k == 1:
        # D_T + D_mig repriced per layer (bit-identical to total_delay):
        # a whole-graph evaluation per migrated block is quadratic in
        # blocks at full model depth
        delay = LayeredTotalDelay(prev, blocks, cost, net, tau)
        delay.update(current)
        cur_val = delay.total()
    else:
        cur_val = pipelined_total_delay(prev, current, blocks, cost, net,
                                        tau, k=k)
    for i in np.flatnonzero(current != prev):
        src, dst = int(current[i]), int(prev[i])
        if not net.is_active(dst):
            continue  # forced evacuation: reverting would re-kill the block
        use[src] -= mem[i]
        use[dst] += mem[i]
        if not np.all(use <= usable):
            use[src] += mem[i]
            use[dst] -= mem[i]
            continue
        if k == 1:
            val = delay.total_with(int(i), dst)
        else:
            trial = current.copy()
            trial[i] = dst
            val = pipelined_total_delay(prev, trial, blocks, cost, net, tau,
                                        k=k)
        if val <= cur_val - min_gain:
            current[i] = dst
            cur_val = val
            if k == 1:
                delay.update(current, [i])
        else:
            use[src] += mem[i]
            use[dst] -= mem[i]
    return current


def memory_usage(place: np.ndarray, blocks: Sequence[Block],
                 cost: CostModel, net: DeviceNetwork, tau: int) -> np.ndarray:
    use = np.zeros(net.n_devices)
    for bl in blocks:
        use[place[bl.index]] += cost.memory(bl, tau)
    return use


def memory_feasible(place: np.ndarray, blocks: Sequence[Block],
                    cost: CostModel, net: DeviceNetwork, tau: int) -> bool:
    """Feasible against the *usable* memory view: observed availability,
    zero on inactive devices — so any placement still referencing a dead
    device is infeasible by construction."""
    return bool(np.all(memory_usage(place, blocks, cost, net, tau)
                       <= net.mem_usable() + 1e-9))
