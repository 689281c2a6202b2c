"""The interval controller at full model depth: musicgen-large's block
graph (48 layers x (32 heads + proj + ffn) = 1,632 blocks) on 4 devices,
and the per-layer delay repricing that keeps it inside ``t_max``."""
import numpy as np
import pytest

from repro.core.blocks import CostModel
from repro.core.controller import ControllerConfig, IntervalController
from repro.core.delay import (LayeredTotalDelay, memory_feasible,
                              memory_usage, pipelined_total_delay,
                              revert_unpaying_migrations, total_delay)
from repro.core.network import DeviceNetwork


def test_controller_plans_musicgen_large_depth_within_t_max():
    """Three intervals, a 20x straggler on device 0 from the second on:
    every plan is feasible, the assigner stays under half of t_max, and
    the controller moves heads off the straggler."""
    cost = CostModel(d_model=2048, n_heads=32, L0=8, n_layers=48, lam=8,
                     compute_mode="incremental", layer_mode="graph",
                     page_size=64)
    net = DeviceNetwork.sample(4, seed=1)
    ctl = IntervalController(32, cost, net,
                             ControllerConfig(lam=8, heads_per_slot=8))
    assert len(ctl.blocks) == 48 * 34
    plans = []
    for it in range(3):
        if it == 1:
            net.inject_straggler(0, slowdown=20.0)
        net.step_background_load()
        plans.append(ctl.step_interval(tau=12 + it))
    assert not any(p["infeasible"] for p in plans)
    assert max(p["assign_s"] for p in plans) < ctl.assigner.t_max / 2
    assert sum(len(p["migrations"]) for p in plans) >= 1
    assert ctl.head_counts()[0] < ctl.head_counts(plans[0]["place"])[0]


def _graph(n_experts: int):
    cost = CostModel(d_model=256, n_heads=4, n_layers=3, lam=4, L0=16,
                     layer_mode="graph", compute_mode="incremental",
                     n_experts=n_experts, d_ff=512)
    return cost, cost.make_blocks()


@pytest.mark.parametrize("n_experts", [0, 4])
def test_layered_total_delay_is_total_delay_bit_for_bit(n_experts):
    cost, blocks = _graph(n_experts)
    net = DeviceNetwork.sample(4, seed=3)
    rng = np.random.default_rng(0)
    prev = rng.integers(0, 4, len(blocks))
    delay = LayeredTotalDelay(prev, blocks, cost, net, 7)
    for _ in range(30):
        place = prev.copy()
        moved = rng.random(len(blocks)) < 0.3
        place[moved] = rng.integers(0, 4, moved.sum())
        delay.update(place)
        assert delay.total() == total_delay(prev, place, blocks, cost,
                                            net, 7)
        i, j = int(rng.integers(len(blocks))), int(rng.integers(4))
        trial = place.copy()
        trial[i] = j
        assert delay.total_with(i, j) == total_delay(prev, trial, blocks,
                                                     cost, net, 7)


def _revert_reference(prev, place, blocks, cost, net, tau, k, min_gain):
    """The whole-graph filter: reprice everything for every move."""
    current = place.copy()
    cur = pipelined_total_delay(prev, current, blocks, cost, net, tau, k=k)
    for i in np.flatnonzero(current != prev):
        if not net.is_active(int(prev[i])):
            continue
        trial = current.copy()
        trial[i] = prev[i]
        if not memory_feasible(trial, blocks, cost, net, tau):
            continue
        val = pipelined_total_delay(prev, trial, blocks, cost, net, tau,
                                    k=k)
        if val <= cur - min_gain:
            current, cur = trial, val
    return current


@pytest.mark.parametrize("n_experts,k", [(0, 1), (4, 1), (0, 2)])
def test_revert_filter_matches_whole_graph_reference(n_experts, k):
    cost, blocks = _graph(n_experts)
    rng = np.random.default_rng(1)
    for seed in range(8):
        net = DeviceNetwork.sample(4, seed=seed)
        if seed % 3 == 0:
            net.inject_straggler(seed % 4, slowdown=20.0)
        if seed % 4 == 1:
            net.fail(3)
        prev = rng.integers(0, 4, len(blocks))
        place = prev.copy()
        moved = rng.random(len(blocks)) < 0.5
        place[moved] = rng.integers(0, 3, moved.sum())
        if seed % 2:
            # memory a little above what ``place`` holds: reverts compete
            net.mem_capacity = memory_usage(place, blocks, cost, net,
                                            9) * 1.6 + 1.0
        got = revert_unpaying_migrations(prev, place, blocks, cost, net, 9,
                                         k=k, min_gain=1e-5)
        want = _revert_reference(prev, place, blocks, cost, net, 9, k,
                                 1e-5)
        np.testing.assert_array_equal(got, want)
