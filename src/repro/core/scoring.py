"""Scoring function S(i,j,τ) — paper §IV.A(a), generalized per-layer.

  S(i,j,τ) = max{ m_i(τ)/M_j(τ),  b_i(τ)/C_j(τ)·(1/T_budget),  CommFactor }

The paper leaves two scalings implicit; we make them explicit and testable:

 - the compute ratio b_i/C_j has units of seconds, while m_i/M_j is
   dimensionless.  A device is "individually feasible" when S <= 1, so the
   time-like terms are normalized by ``deadline`` — the wall-clock budget of
   one interval (the paper sizes intervals "on the order of a few seconds";
   default 5 s, exposed as a parameter and swept in the tests).

 - CommFactor(i,j,τ) "approximates data transfer times if i must exchange
   information with blocks on different devices".  On a per-layer block
   graph every counterpart is layer-local except the inter-layer edges:
   head(l,i) receives its input from ffn(l-1) (the controller for l=0) and
   sends to proj(l); proj(l) takes the max of inbound-head and
   outbound-ffn transfers; ffn(l) the max of the inbound transfer and the
   outbound ffn(l) → head(l+1,·) activation broadcast — all normalized by
   the same deadline.  Counterpart devices are read from the *previous*
   placement (the controller's best current knowledge).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from repro.core.blocks import Block, CostModel, EXPERT, HEAD, PROJ, graph_of
from repro.core.network import DeviceNetwork


def comm_factor(block: Block, j: int, blocks: Sequence[Block],
                prev_place: Optional[np.ndarray], cost: CostModel,
                net: DeviceNetwork, tau: int, deadline: float) -> float:
    def rate(a, b):
        return np.inf if a == b else float(net.bandwidth[a, b])

    g = graph_of(blocks)
    l = block.layer

    def dev(b: Block) -> int:
        """Counterpart device, -1 when unknown.  ``prev_place`` may be a
        partial view (entries -1): the assigner overlays its tentative
        in-round placement on the previous interval's — the controller's
        best current knowledge (§IV.A(a)) — so the first interval is not
        comm-blind for counterparts already placed this round."""
        if prev_place is None:
            return -1
        return int(prev_place[b.index])

    if block.kind == HEAD:
        t = 0.0
        if l == 0:
            t += cost.input_bytes(tau) / rate(net.controller, j)
        else:
            # inbound activation: the dense ffn, or the load-weighted
            # expert combine fan-in (sources with unknown devices skipped)
            for src_bl in g.out_blocks(l - 1):
                src = dev(src_bl)
                if src < 0:
                    continue
                fr = 1.0 if src_bl.kind != EXPERT \
                    else cost.expert_load(src_bl)
                t += fr * cost.interlayer_bytes(tau) / rate(src, j)
        proj_dev = dev(g.proj[l])
        if proj_dev >= 0:
            t += cost.head_to_proj_bytes(tau) / rate(j, proj_dev)
        return t / deadline
    if block.kind == PROJ:
        head_devs = set(d for d in (dev(h) for h in g.heads[l]) if d >= 0)
        t_in = cost.head_to_proj_bytes(tau) * cost.n_heads  # worst-case inbound
        t = 0.0
        if head_devs:
            t = t_in / min(rate(h_dev, j) for h_dev in head_devs)
        for out_bl in g.out_blocks(l):
            out_dev = dev(out_bl)
            if out_dev < 0:
                continue
            fr = 1.0 if out_bl.kind != EXPERT else cost.expert_load(out_bl)
            t = max(t, fr * cost.proj_to_ffn_bytes(tau) / rate(j, out_dev))
        return t / deadline
    if block.kind == EXPERT:
        # router fan-out in (load-fraction share of the proj activation),
        # combine out (same share of the next layer's activation broadcast)
        fr = cost.expert_load(block)
        t = 0.0
        proj_dev = dev(g.proj[l])
        if proj_dev >= 0:
            t = fr * cost.proj_to_ffn_bytes(tau) / rate(proj_dev, j)
        if l + 1 < g.n_layers:
            next_devs = [rate(j, d) for d in (dev(h) for h in g.heads[l + 1])
                         if d >= 0]
            if next_devs:
                t = max(t, fr * cost.interlayer_bytes(tau) / min(next_devs))
        return t / deadline
    # ffn: inbound from proj(l), outbound broadcast to layer l+1's heads
    t = 0.0
    proj_dev = dev(g.proj[l])
    if proj_dev >= 0:
        t = cost.proj_to_ffn_bytes(tau) / rate(proj_dev, j)
    if l + 1 < g.n_layers:
        next_devs = [rate(j, d) for d in (dev(h) for h in g.heads[l + 1])
                     if d >= 0]
        if next_devs:
            t = max(t, cost.interlayer_bytes(tau) / min(next_devs))
    return t / deadline


def score(block: Block, j: int, blocks: Sequence[Block],
          prev_place: Optional[np.ndarray], cost: CostModel,
          net: DeviceNetwork, tau: int, *, deadline: float = 5.0,
          mem_used: Optional[np.ndarray] = None,
          compute_used: Optional[np.ndarray] = None) -> float:
    """S(i,j,τ).  ``mem_used``/``compute_used`` optionally subtract already-
    assigned load on j (the per-block score in the paper is load-free; the
    algorithm's constraint check handles concurrency — §IV.A)."""
    if not net.is_active(j):
        # inactive device: no block may land here — enforced, not priced
        return np.inf
    mem_cap = net.mem_avail[j] - (0.0 if mem_used is None else mem_used[j])
    if mem_cap <= 0:
        return np.inf
    comp_avail = net.compute_avail[j]
    if comp_avail <= 0:
        return np.inf
    mem_term = cost.memory(block, tau) / mem_cap
    comp_term = (cost.compute(block, tau) +
                 (0.0 if compute_used is None else compute_used[j])) \
        / comp_avail / deadline
    cf = comm_factor(block, j, blocks, prev_place, cost, net, tau, deadline)
    return float(max(mem_term, comp_term, cf))


def _comm_times(block: Block, blocks: Sequence[Block],
                prev_place: Optional[np.ndarray], cost: CostModel,
                net: DeviceNetwork, tau: int) -> List[float]:
    """``comm_factor``'s transfer time (before the deadline divide) on
    every device, with the counterpart devices and byte volumes read once;
    each device's arithmetic is ``comm_factor``'s, in its order."""
    bw = net.bandwidth

    def rate(a, b):
        return np.inf if a == b else float(bw[a, b])

    g = graph_of(blocks)
    l = block.layer
    devices = range(net.n_devices)

    def dev(b: Block) -> int:
        return -1 if prev_place is None else int(prev_place[b.index])

    def fraction(b: Block) -> float:
        return 1.0 if b.kind != EXPERT else cost.expert_load(b)

    if block.kind == HEAD:
        if l == 0:
            ins = [(net.controller, cost.input_bytes(tau))]
        else:
            ins = [(dev(b), fraction(b) * cost.interlayer_bytes(tau))
                   for b in g.out_blocks(l - 1) if dev(b) >= 0]
        proj_dev = dev(g.proj[l])
        w_out = cost.head_to_proj_bytes(tau)
        out = []
        for j in devices:
            t = 0.0
            for src, w in ins:
                t += w / rate(src, j)
            if proj_dev >= 0:
                t += w_out / rate(j, proj_dev)
            out.append(t)
        return out
    if block.kind == PROJ:
        head_devs = set(d for d in (dev(h) for h in g.heads[l]) if d >= 0)
        t_in = cost.head_to_proj_bytes(tau) * cost.n_heads
        outs = [(dev(b), fraction(b) * cost.proj_to_ffn_bytes(tau))
                for b in g.out_blocks(l) if dev(b) >= 0]
        out = []
        for j in devices:
            t = 0.0
            if head_devs:
                t = t_in / min(rate(h_dev, j) for h_dev in head_devs)
            for out_dev, w in outs:
                t = max(t, w / rate(j, out_dev))
            out.append(t)
        return out
    # ffn / expert: inbound from proj(l), outbound to layer l+1's heads
    # (an ffn carries the whole activation: 1.0 x bytes is exact)
    fr = fraction(block)
    w_in = fr * cost.proj_to_ffn_bytes(tau)
    w_out = fr * cost.interlayer_bytes(tau)
    proj_dev = dev(g.proj[l])
    next_devs = set() if l + 1 >= g.n_layers else \
        set(d for d in (dev(h) for h in g.heads[l + 1]) if d >= 0)
    out = []
    for j in devices:
        t = 0.0
        if proj_dev >= 0:
            t = w_in / rate(proj_dev, j)
        if next_devs:
            t = max(t, w_out / min(rate(j, d) for d in next_devs))
        out.append(t)
    return out


def block_scores(block: Block, blocks: Sequence[Block],
                 prev_place: Optional[np.ndarray], cost: CostModel,
                 net: DeviceNetwork, tau: int, *, deadline: float = 5.0,
                 mem_used: Optional[np.ndarray] = None,
                 compute_used: Optional[np.ndarray] = None) -> List[float]:
    """``[score(block, j, ...) for j in range(V)]``, bit for bit: what does
    not depend on the device (the block's memory and compute, its byte
    volumes, its counterparts' devices) is read once."""
    V = net.n_devices
    comm = _comm_times(block, blocks, prev_place, cost, net, tau)
    m_i, b_i = cost.memory(block, tau), cost.compute(block, tau)
    mem_used = [0.0] * V if mem_used is None else mem_used.tolist()
    compute_used = [0.0] * V if compute_used is None \
        else compute_used.tolist()
    out = []
    for active, mem_avail, comp_avail, m_used, c_used, t in zip(
            net.active.tolist(), net.mem_avail.tolist(),
            net.compute_avail.tolist(), mem_used, compute_used, comm):
        mem_cap = mem_avail - m_used
        if not active or mem_cap <= 0 or comp_avail <= 0:
            out.append(np.inf)
            continue
        out.append(max(m_i / mem_cap,
                       (b_i + c_used) / comp_avail / deadline,
                       t / deadline))
    return out


def score_matrix(blocks: Sequence[Block], prev_place: Optional[np.ndarray],
                 cost: CostModel, net: DeviceNetwork, tau: int,
                 *, deadline: float = 5.0) -> np.ndarray:
    """(|B|, |V|) matrix of S(i,j,τ)."""
    S = np.empty((len(blocks), net.n_devices))
    for bl in blocks:
        for j in range(net.n_devices):
            S[bl.index, j] = score(bl, j, blocks, prev_place, cost, net, tau,
                                   deadline=deadline)
    return S
