"""Async serving runtime: bit-identity with the synchronous engine,
typed backpressure, clean drain, and worker-hang detection through the
fault-tolerance heartbeat monitor."""
import asyncio

import numpy as np
import pytest

from repro.serving.async_runtime import (AsyncServingEngine,
                                         QueueFullError)
from repro.serving.engine import ServingEngine, WaveServingEngine
from repro.serving.workload import (VirtualClock, drive_virtual,
                                    make_workload)
from tests.conftest import reduced_config


def _cfg():
    return reduced_config("llama3-8b")


def _engine(cfg, **kw):
    return ServingEngine(cfg, n_slots=2, max_seq=64, lam=10 ** 9,
                         seed=0, **kw)


def _workload(cfg, rate=0.3, horizon=30.0, seed=5):
    return make_workload("poisson", rate=rate, horizon=horizon, seed=seed,
                         vocab=cfg.vocab_size)


async def _run_async(eng, reqs, **rt_kw):
    rt = AsyncServingEngine(eng, queue_limit=len(reqs) + 1, **rt_kw)
    async with rt:
        handles = [rt.submit(r.prompt, max_new_tokens=r.max_new_tokens)
                   for r in sorted(reqs, key=lambda r: r.t_arrival)]
        await rt.drain()
    return handles


# ------------------------------------------------------------ bit-identity
@pytest.mark.parametrize("kw", [{}, {"paged": True, "page_size": 8}],
                         ids=["dense", "paged"])
def test_async_streams_bit_identical_to_sync(kw):
    """The tentpole contract: same admission order => every per-request
    token stream equals the synchronous engine's, dense and paged."""
    cfg = _cfg()
    reqs = _workload(cfg)
    sync = drive_virtual(_engine(cfg, **kw), reqs)
    assert sync["n_finished"] == len(reqs)
    handles = asyncio.run(_run_async(_engine(cfg, **kw), reqs))
    assert {h.rid: h.tokens for h in handles} == sync["streams"]
    for h in handles:
        assert h.error is None
        assert h.t_first is not None and h.t_done >= h.t_first


def test_stream_iteration_matches_result():
    """The async-generator view and the awaited result view agree."""
    cfg = _cfg()
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size

    async def go():
        async with AsyncServingEngine(_engine(cfg)) as rt:
            h = rt.submit(prompt, max_new_tokens=6)
            seen = [tok async for tok in h.stream()]
            return seen, await h.result()

    seen, result = asyncio.run(go())
    assert seen == result and len(seen) == 6


# ------------------------------------------------------------ backpressure
def test_queue_full_is_typed_reject():
    cfg = _cfg()
    eng = _engine(cfg)
    rt = AsyncServingEngine(eng, queue_limit=2)
    p = np.arange(4, dtype=np.int32)
    rt.submit(p), rt.submit(p)
    assert rt.queue_depth == 2
    with pytest.raises(QueueFullError, match="admission queue full"):
        rt.submit(p)
    # nothing was enqueued by the rejected call
    assert rt.queue_depth == 2
    assert len(eng.queue) == 0          # runtime never started


def test_submit_after_drain_rejected():
    cfg = _cfg()

    async def go():
        rt = AsyncServingEngine(_engine(cfg))
        async with rt:
            rt.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
            await rt.drain()
            with pytest.raises(RuntimeError, match="draining"):
                rt.submit(np.arange(4, dtype=np.int32))

    asyncio.run(go())


def test_oversized_prompt_fails_its_own_handle():
    """An intake reject (prompt longer than the biggest bucket) surfaces
    on THAT request's stream; the runtime and other requests live on."""
    cfg = _cfg()

    async def go():
        async with AsyncServingEngine(_engine(cfg)) as rt:
            ok = rt.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
            bad = rt.submit(np.zeros(500, np.int32), max_new_tokens=3)
            with pytest.raises(ValueError):
                await bad.result()
            return await ok.result()

    assert len(asyncio.run(go())) == 3


# -------------------------------------------------------------------- drain
def test_drain_leaves_no_live_pages():
    cfg = _cfg()
    reqs = _workload(cfg, rate=0.5)
    eng = _engine(cfg, paged=True, page_size=8)
    handles = asyncio.run(_run_async(eng, reqs))
    assert all(h._finished.is_set() for h in handles)
    assert len(eng.queue) == 0 and not eng._active()
    for a in eng.allocators:
        a.check_invariants()
        assert a.live_pages == 0 and a.reserved_pages == 0


def test_runtime_requires_slot_engine_and_free_sink():
    cfg = _cfg()
    weng = WaveServingEngine(cfg, n_slots=2, max_seq=48, lam=10 ** 9,
                             seed=0)
    with pytest.raises(TypeError, match="ServingEngine"):
        AsyncServingEngine(weng)
    eng = _engine(cfg)
    eng.token_sink = lambda r, t, d: None
    with pytest.raises(ValueError, match="token_sink"):
        AsyncServingEngine(eng)


# ----------------------------------------------------------- hang detection
def test_hung_worker_detected_and_logged_once():
    """The formerly-orphaned HeartbeatMonitor now guards the serving
    path: a worker silent past the timeout is flagged exactly once,
    logged into the monitor's event log, and revives on heartbeat."""
    clk = VirtualClock()
    rt = AsyncServingEngine(_engine(_cfg()), heartbeat_timeout=5.0,
                            heartbeat_clock=clk.now)
    assert rt.check_workers() == []
    clk.advance(6.0)
    assert sorted(rt.check_workers()) == [rt.ADMISSION, rt.DECODE]
    assert rt.check_workers() == []          # one-shot, not per-poll
    hung = [e for e in rt.monitor.events if e["kind"] == "worker_hung"]
    assert len(hung) == 2
    assert all(e["silent_s"] > 5.0 for e in hung)
    # a late heartbeat revives the worker; going silent again re-flags it
    rt.monitor.record_heartbeat(rt.DECODE)
    clk.advance(6.0)
    assert rt.check_workers() == [rt.DECODE]


def test_live_workers_heartbeat_under_load():
    """After a real drain the workers have been heartbeating: nobody is
    flagged hung and the decode worker accumulated step telemetry."""
    cfg = _cfg()
    reqs = _workload(cfg)
    eng = _engine(cfg)
    rt_holder = {}

    async def go():
        rt = AsyncServingEngine(eng, queue_limit=len(reqs) + 1)
        rt_holder["rt"] = rt
        async with rt:
            for r in sorted(reqs, key=lambda r: r.t_arrival):
                rt.submit(r.prompt, max_new_tokens=r.max_new_tokens)
            await rt.drain()

    asyncio.run(go())
    rt = rt_holder["rt"]
    assert rt.check_workers() == []
    assert len(rt.monitor.slots[rt.DECODE].step_times) > 0


# ------------------------------------------------------- load observability
def test_interval_log_carries_arrival_rate_and_queue_depth():
    """The controller's interval records now include the engine's
    observed load — the signal the traffic-adaptive search will use."""
    cfg = _cfg()
    eng = ServingEngine(cfg, n_slots=2, max_seq=64, lam=8, seed=0)
    reqs = _workload(cfg, rate=0.4, horizon=25.0, seed=3)
    drive_virtual(eng, reqs)
    assert eng.migration_log, "lam=8 must tick at least one interval"
    for entry in eng.migration_log:
        assert entry["arrival_rate"] is not None
        assert entry["arrival_rate"] >= 0.0
        assert entry["queue_depth"] is not None
    # arrivals per step summed over intervals ~ total submissions
    assert sum(m["arrival_rate"] for m in eng.migration_log) > 0.0
