"""Algorithm 1's placements, pinned: every interval's raw ``assign`` output
and final ``plan["place"]`` hash to the values recorded before its host
work was cut (batched tie pricing on prefix sums, per-block scoring, the
tentative view kept where the placement is written), and the fast paths
equal their plain references bit for bit."""
import hashlib

import numpy as np
import pytest

from repro.core.blocks import CostModel
from repro.core.controller import ControllerConfig, IntervalController
from repro.core.delay import LayeredTotalDelay, total_delay
from repro.core.network import DeviceNetwork
from repro.core.scoring import block_scores, score

# geometry, controller, and what happens to the 4-device network; every
# case runs 10 intervals at tau = 12 + it with a 20x straggler on device
# 0 from it = 1
CASES = {
    # musicgen-large's controller: 48 x (32 heads + proj + ffn) blocks
    "musicgen_depth": dict(cost=dict(d_model=2048, n_heads=32, n_layers=48),
                           ctl=dict(heads_per_slot=8)),
    # glm4-9b's: 20 layers, GQA groups of 16 query heads
    "glm_geometry": dict(cost=dict(d_model=4096, n_heads=32, n_layers=20),
                         ctl=dict(heads_per_slot=8, group_size=16)),
    "experts": dict(cost=dict(d_model=1024, n_heads=16, n_layers=8,
                              n_experts=4, d_ff=2048),
                    ctl=dict(heads_per_slot=4)),
    # device 3 fails at it = 4
    "failed_device": dict(cost=dict(d_model=2048, n_heads=32, n_layers=12),
                          ctl=dict(heads_per_slot=8), fail=(4, 3)),
    # memory at 3.4% of capacity: overload resolution runs, and the last
    # two intervals are infeasible
    "tight_memory": dict(cost=dict(d_model=2048, n_heads=32, n_layers=12),
                         ctl=dict(heads_per_slot=8), mem_scale=0.034),
}

# md5 of each interval's int64 bytes, first 12 hex digits ("none" where
# assign found no placement)
EXPECTED = {
    "musicgen_depth": (
        ["0d25bc88e18b", "ac64bdeb2de4", "3bb4edc0ed61", "9cf8ad58926e",
         "382508763492", "ecf894e2ce2b", "707863692c7e", "244387b7bfbb",
         "94eb2f6c45a0", "f4adb5b43d71"],
        ["0d25bc88e18b", "a350ecb74bdd", "9a0d4579a764", "b11bd504e378",
         "291adaedfc70", "155b67cc7891", "155b67cc7891", "155b67cc7891",
         "c3250ddaf6c2", "fbd54bb2efc2"]),
    "glm_geometry": (
        ["a1436fbad92f", "6bbce21baee7", "792f39f72283", "d230e56a899c",
         "cee11aa8027e", "6f6756ed1389", "81e1d857c030", "088f9ecf21f0",
         "ec584bcfac48", "d35be0bf1b13"],
        ["a1436fbad92f", "def727dce957", "1081b2b6ec7c", "1081b2b6ec7c",
         "60d0cbe29a2d", "54bfb295f432", "54bfb295f432", "54bfb295f432",
         "dac8dfb09cc5", "67bb0f7d8a6d"]),
    "experts": (
        ["f19ed5c77b02", "10d896e426be", "b428efac2b53", "92e07c9b0db8",
         "c68cc811fe45", "aaa6d39a5c72", "5091871a6983", "dda6cb9e5421",
         "003d9cbd2d10", "f2b9eba3e46d"],
        ["f19ed5c77b02", "b646e9b6e0c2", "cffc3823e63d", "cffc3823e63d",
         "cffc3823e63d", "cffc3823e63d", "cffc3823e63d", "cffc3823e63d",
         "abca7b58e3f8", "abca7b58e3f8"]),
    "failed_device": (
        ["71bf580e0b95", "1d15f3d221e4", "0f737802f8f3", "8372e47002cc",
         "39ff3c7d4803", "e12b37a8c744", "70353f325722", "5dc0410a0884",
         "2e043f45373d", "f6eab0314ba1"],
        ["71bf580e0b95", "a9c6a36d5f72", "766181b443d0", "766181b443d0",
         "cfd3f4cfd065", "cfd3f4cfd065", "0224ef0ba203", "0224ef0ba203",
         "0224ef0ba203", "0224ef0ba203"]),
    "tight_memory": (
        ["dcd7aac351c6", "e5c51e869ddf", "5456e7293078", "9c9e8e662f8b",
         "510cd5ff7d08", "a7de0d079500", "2c2d9f35e4da", "9595402402b8",
         "none", "none"],
        ["dcd7aac351c6", "f6642bc6e7db", "907efd95303d", "d88b18d03b57",
         "2dae3ed23a55", "2663ac144952", "0737295f0c20", "c577340114a7",
         "c577340114a7", "c577340114a7"]),
}


def _digest(a) -> str:
    if a is None:
        return "none"
    raw = np.asarray(a, dtype=np.int64).tobytes()
    return hashlib.md5(raw).hexdigest()[:12]


@pytest.mark.parametrize("case", list(CASES))
def test_controller_placements_are_unchanged(case):
    c = CASES[case]
    cost = CostModel(L0=8, lam=8, compute_mode="incremental",
                     layer_mode="graph", page_size=64, **c["cost"])
    net = DeviceNetwork.sample(4, seed=1)
    net.mem_capacity = net.mem_capacity * c.get("mem_scale", 1.0)
    ctl = IntervalController(c["cost"]["n_heads"], cost, net,
                             ControllerConfig(lam=8, **c["ctl"]))
    raw = []
    assign = ctl.assigner.assign

    def recorded(*a, **kw):
        place, stats = assign(*a, **kw)
        raw.append(_digest(place))
        return place, stats

    ctl.assigner.assign = recorded
    places = []
    for it in range(10):
        if it == 1:
            net.inject_straggler(0, slowdown=20.0)
        if it == c.get("fail", (None,))[0]:
            net.fail(c["fail"][1])
        net.step_background_load()
        places.append(_digest(ctl.step_interval(tau=12 + it)["place"]))
    assert (raw, places) == EXPECTED[case]


def _graph(n_experts: int):
    cost = CostModel(d_model=256, n_heads=4, n_layers=5, lam=4, L0=16,
                     layer_mode="graph", compute_mode="incremental",
                     n_experts=n_experts, d_ff=512)
    return cost, cost.make_blocks()


@pytest.mark.parametrize("n_experts,failed", [(0, False), (4, False),
                                              (0, True), (4, True)])
def test_batched_tie_keys_are_total_delay_bit_for_bit(n_experts, failed):
    """Every key of ``totals_with`` — heads, proj, ffn and experts, on
    candidate sets that do and do not hold the block's own device — equals
    the whole-graph ``total_delay`` of that placement."""
    cost, blocks = _graph(n_experts)
    net = DeviceNetwork.sample(4, seed=3)
    net.inject_straggler(1, slowdown=20.0)
    if failed:
        net.fail(2)
    rng = np.random.default_rng(0)
    prev = rng.integers(0, 4, len(blocks))
    delay = LayeredTotalDelay(prev, blocks, cost, net, 7)
    last = np.zeros(len(blocks), dtype=bool)
    for it in range(40):
        place = prev.copy()
        moved = rng.random(len(blocks)) < 0.3
        place[moved] = rng.integers(0, 4, moved.sum())
        # every block that may differ from the adopted placement, or none
        delay.update(place, np.flatnonzero(moved | last) if it % 2 else None)
        last = moved
        assert delay.total() == total_delay(prev, place, blocks, cost,
                                            net, 7)
        for i in rng.choice(len(blocks), 6, replace=False).tolist():
            js = rng.permutation(4)[:int(rng.integers(1, 5))].tolist()
            want = []
            for j in js:
                trial = place.copy()
                trial[i] = j
                want.append(total_delay(prev, trial, blocks, cost, net, 7))
            assert delay.totals_with(i, js) == want


@pytest.mark.parametrize("n_experts", [0, 4])
def test_block_scores_are_score_bit_for_bit(n_experts):
    """``block_scores`` equals ``score`` on every device, on partial views
    (-1 = not yet placed), with an inactive device, devices whose memory
    is used up (``mem_cap <= 0``), and with no view or load vectors."""
    cost, blocks = _graph(n_experts)
    rng = np.random.default_rng(1)
    for trial in range(12):
        net = DeviceNetwork.sample(4, seed=trial)
        if trial % 3 == 1:
            net.fail(int(rng.integers(4)))
        view = rng.integers(-1, 4, len(blocks))
        mem_used = net.mem_avail * rng.uniform(0.0, 1.2, 4)
        mem_used[trial % 4] = net.mem_avail[trial % 4]   # mem_cap == 0
        comp_used = net.compute_avail * rng.uniform(0.0, 2.0, 4)
        loads = [dict(mem_used=mem_used, compute_used=comp_used),
                 dict(mem_used=None, compute_used=None)]
        for bl in blocks:
            for kw, v in zip(loads, (view, None)):
                want = [score(bl, j, blocks, v, cost, net, 9,
                              deadline=0.8, **kw) for j in range(4)]
                assert block_scores(bl, blocks, v, cost, net, 9,
                                    deadline=0.8, **kw) == want
