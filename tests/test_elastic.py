"""Elastic device churn: network fail/slow/join/rejoin semantics, the
controller's evacuation/expansion plans, and the serving engine's
mid-decode recovery (teacher-forced replay => surviving streams are
bit-identical to a churn-free run, zero client-visible tokens lost)."""
import numpy as np
import pytest

from repro.core import DeviceNetwork
from repro.core.blocks import CostModel
from repro.core.controller import ControllerConfig, IntervalController
from repro.serving.async_runtime import AsyncServingEngine
from repro.serving.engine import ServingEngine
from repro.serving.workload import VirtualClock, drive_virtual, make_workload
from tests.conftest import reduced_config


# ---------------------------------------------------------------- network
def test_network_churn_transitions_and_errors():
    net = DeviceNetwork.sample(4, seed=0)
    net.fail(2)
    assert not net.is_active(2) and net.n_active == 3
    assert 2 not in net.active_ids
    assert net.compute_avail[2] == 0.0
    assert net.mem_usable()[2] == 0.0
    # slow on a dead device is a no-op; on a live one it pins load
    net.slow(2, 4.0)
    assert net.compute_avail[2] == 0.0
    net.slow(1, 4.0)
    assert net.compute_avail[1] == pytest.approx(net.compute_max[1] / 4.0)
    with pytest.raises(ValueError):
        net.slow(1, 0.5)
    # rejoin restores full, fresh capacity
    net.rejoin(2)
    assert net.is_active(2)
    assert net.compute_avail[2] == net.compute_max[2]
    # join appends a device with symmetric links
    j = net.join(1e9, 2e9, np.full(4, 1e8))
    assert j == 4 and net.n_devices == 5 and net.is_active(4)
    assert np.all(net.bandwidth[4, :4] == net.bandwidth[:4, 4])
    assert np.isinf(net.bandwidth[4, 4])
    with pytest.raises(ValueError):
        net.join(1e9, 2e9, np.full(3, 1e8))       # wrong bw_row length
    with pytest.raises(ValueError):
        net.join(-1.0, 2e9, np.full(5, 1e8))      # non-positive resources
    # background-load stepping skips inactive devices but keeps the rest
    net.fail(1)
    before = net.compute_avail[1]
    net.step_background_load()
    assert net.compute_avail[1] == before == 0.0


# ------------------------------------------------------------- controller
def _controller(net, n_heads=8, hps=2, lam=16):
    cost = CostModel(d_model=256, n_heads=n_heads, L0=8, lam=lam,
                     n_layers=2, layer_mode="graph",
                     compute_mode="incremental")
    return IntervalController(n_heads, cost, net,
                              ControllerConfig(lam=lam, heads_per_slot=hps))


def test_handle_failure_evacuates_dead_device():
    net = DeviceNetwork.sample(4, seed=1)
    ctl = _controller(net)
    ctl.step_interval()
    plan = ctl.handle_failure(2)
    assert plan["evacuation"] and plan["failed_device"] == 2
    assert not np.any(np.asarray(plan["place"]) == 2)
    assert not net.is_active(2)
    assert plan["evacuation"] is True
    # a later interval still never places on the dead device
    plan2 = ctl.step_interval()
    assert not np.any(np.asarray(plan2["place"]) == 2)


def test_handle_failure_infeasible_raises():
    """Survivors that cannot hold the dead device's blocks must fail
    loudly, not silently keep serving from a corpse."""
    big, tiny = 1e12, 10.0
    net = DeviceNetwork(
        mem_capacity=np.array([big, tiny, tiny]),
        compute_max=np.full(3, 1e9), compute_avail=np.full(3, 1e9),
        bandwidth=np.where(np.eye(3, dtype=bool), np.inf, 1e9),
        rng=np.random.default_rng(0))
    ctl = _controller(net, n_heads=3, hps=1)
    ctl.step_interval()
    assert np.all(np.asarray(ctl.place) == 0)     # only device 0 fits
    with pytest.raises(RuntimeError, match="evacuation infeasible"):
        ctl.handle_failure(0)


def test_handle_rejoin_emits_expansion_plan():
    net = DeviceNetwork.sample(4, seed=1)
    ctl = _controller(net)
    ctl.step_interval()
    ctl.handle_failure(2)
    plan = ctl.handle_rejoin(2)
    assert plan["expansion"] and plan["rejoined_device"] == 2
    assert net.is_active(2)


# ----------------------------------------------------------------- engine
def _churn_run(cfg, churn, lam=4, paged=False, **ekw):
    """Run 5 staggered requests on 2 slots, firing ``churn`` (a
    {decode_step: fn(eng)} dict) as the scheduler crosses each step."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, size=n) for n in (5, 11, 8, 14, 6)]
    eng = ServingEngine(cfg, n_slots=2, max_seq=64, lam=lam, seed=0,
                        paged=paged, **ekw)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=6 + 3 * (i % 2))
    ev = dict(churn)
    while True:
        if eng.decode_steps in ev:
            ev.pop(eng.decode_steps)(eng)
        if not eng.step():
            break
    assert not ev, f"unfired churn events at steps {sorted(ev)}"
    return {r.rid: r.out_tokens for r in eng.finished}, eng


def test_fail_device_mid_decode_streams_bit_identical():
    """Kill a device while slots sit at unequal depths: the evacuation +
    teacher-forced replay must leave every surviving stream bit-identical
    to a run with no churn and no controller at all."""
    cfg = reduced_config("musicgen-large")      # MHA: physical migrations
    ref, _ = _churn_run(cfg, {}, lam=10 ** 9)
    out, eng = _churn_run(cfg, {4: lambda e: e.fail_device(2)})
    assert out == ref and len(out) == 5
    assert not eng.net.is_active(2)
    assert not np.any(np.asarray(eng.controller.place) == 2)
    rec = eng.recovery_log[0]
    assert rec["event"] == "fail" and rec["device"] == 2
    assert rec["tokens_lost"] == 0 and eng.tokens_lost == 0
    assert rec["replayed_slots"] >= 1
    assert rec["replay_prefills"] == rec["replayed_slots"]
    # both slots were mid-decode at step 4, so replay actually decoded
    assert rec["replay_steps"] >= 1
    with pytest.raises(ValueError):
        eng.fail_device(2)                      # already dead


def test_fail_then_rejoin_streams_bit_identical_paged():
    """Same churn through the paged engine: the rebuilt page tables and
    re-admitted allocator must reproduce the streams, and a later rejoin
    (expansion migrations copy KV from survivors — no replay) must not
    disturb them either."""
    cfg = reduced_config("musicgen-large")
    ref, _ = _churn_run(cfg, {}, lam=10 ** 9, paged=True, page_size=8)
    churn = {4: lambda e: e.fail_device(2),
             12: lambda e: e.rejoin_device(2)}
    out, eng = _churn_run(cfg, churn, paged=True, page_size=8)
    assert out == ref and len(out) == 5
    assert eng.net.is_active(2)
    events = [r["event"] for r in eng.recovery_log]
    assert events == ["fail", "rejoin"]
    for alloc in eng.allocators:
        alloc.check_invariants()
    with pytest.raises(ValueError):
        eng.rejoin_device(2)                    # already active


def test_slow_device_migrates_away_streams_unchanged():
    cfg = reduced_config("musicgen-large")
    ref, _ = _churn_run(cfg, {}, lam=10 ** 9)
    out, eng = _churn_run(cfg, {3: lambda e: e.slow_device(1, 50.0)},
                          lam=3)
    assert out == ref
    assert eng.net.compute_avail[1] < eng.net.compute_max[1] / 10


# ------------------------------------------------------------------ async
def test_async_hang_escalates_to_controller_replan():
    """worker_hung must do more than log: the escalation refreshes the
    controller's availability view and forces Algorithm 1 on the next
    scheduler step even under an effectively-infinite λ cadence."""
    cfg = reduced_config("llama3-8b")
    eng = ServingEngine(cfg, n_slots=2, max_seq=48, lam=10 ** 9, seed=0)
    clock = VirtualClock()
    rt = AsyncServingEngine(eng, heartbeat_timeout=5.0,
                            heartbeat_clock=clock.now)
    clock.advance(6.0)
    hung = rt.check_workers()
    assert hung == [rt.ADMISSION, rt.DECODE]
    assert eng._replan_pending
    kinds = [e["kind"] for e in rt.monitor.events]
    assert kinds.count("recovery_escalated") == 2
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=3)
    assert eng.step()
    assert len(eng.migration_log) == 1          # interval fired off-cadence
    assert not eng._replan_pending
    assert not rt.check_workers()               # one-shot transition


def test_async_escalation_can_be_disabled():
    cfg = reduced_config("llama3-8b")
    eng = ServingEngine(cfg, n_slots=2, max_seq=48, lam=10 ** 9, seed=0)
    clock = VirtualClock()
    rt = AsyncServingEngine(eng, heartbeat_timeout=5.0,
                            heartbeat_clock=clock.now,
                            escalate_hangs=False)
    clock.advance(6.0)
    assert rt.check_workers() == [rt.ADMISSION, rt.DECODE]
    assert not eng._replan_pending
    assert all(e["kind"] != "recovery_escalated" for e in rt.monitor.events)


# ----------------------------------------------------------------- driver
def test_drive_virtual_events_and_model_pricing():
    """Churn events fire at their virtual time, model-priced stepping is
    deterministic, and neither changes any token stream."""
    cfg = reduced_config("llama3-8b")
    reqs = make_workload(rate=0.3, horizon=40.0, seed=5)

    def build():
        return ServingEngine(cfg, n_slots=2, max_seq=64, lam=6, seed=0)

    fired = []
    ev = [(10.0, lambda e: fired.append(e.decode_steps))]
    base = drive_virtual(build(), reqs)
    r1 = drive_virtual(build(), reqs, events=ev, price_by_model=True)
    r2 = drive_virtual(build(), reqs, events=list(ev), price_by_model=True)
    assert len(fired) == 2                      # once per priced run
    assert r1["streams"] == r2["streams"] == base["streams"]
    for k in ("p50_ttft", "p99_ttft", "goodput", "t_end"):
        assert r1[k] == r2[k]


def test_drive_virtual_event_fires_in_idle_gap():
    """An event scheduled inside an idle gap (or after the last arrival)
    must still fire — idle time jumps to it."""
    cfg = reduced_config("llama3-8b")
    rng = np.random.default_rng(0)
    from repro.serving.workload import TimedRequest
    reqs = [TimedRequest(0.0, rng.integers(0, 97, size=5).astype(np.int32),
                         3)]
    eng = ServingEngine(cfg, n_slots=2, max_seq=48, lam=10 ** 9, seed=0)
    fired = []
    drive_virtual(eng, reqs, events=[(1000.0, lambda e: fired.append(1))])
    assert fired == [1]
