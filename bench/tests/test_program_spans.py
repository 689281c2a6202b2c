"""``bench/program_spans.py`` and the two readers of the program's spans
(``host_step_ms``, ``assign_share``) on hand-made host planes, the same
reductions over the recorder's ring (``bench/span_report.py``), and the
named-scope reduction on the recorded chip trace."""
from collections import namedtuple
from pathlib import Path

import numpy as np
import pytest

from bench import program_spans as ps
from bench import run as R
from bench import span_report as SR
from bench import trace_reduce as tr
from repro.runtime.spans import Span

FIX = Path(__file__).resolve().parent / "fixtures"
Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns end_ns stats")


def _ev(name, s_ms, e_ms, **stats):
    return Event(name, int(s_ms * 1e6), int(e_ms * 1e6), list(stats.items()))


def _prof(*events, extra=()):
    return namedtuple("Prof", "planes")([Plane(tr.HOST_PLANE, [
        Line("python3", list(events)), Line("other", list(extra))])])


def _read(name, prof, window_s=1.0):
    run = R.Run(cell={}, conf={}, traffic={}, seconds=45.0,
                trace={"prof": prof, "window_s": window_s})
    return R.load_module(R.HERE / "metrics" / f"{name}.py").read(run)


# two decode steps: the first admits a request and plans, the second
# applies a plan that moves heads; an idle step (no decode) is left out
STEPS = (
    _ev("sched.step", 0, 100, arg=7),
    _ev("sched.admit", 1, 21, rid=3, arg=0.25),
    _ev("model.prefill_chunk", 2, 10, rid=3, arg=0),
    _ev("kv.mount", 22, 23, arg=1),
    _ev("model.decode_dispatch", 23, 25),
    _ev("model.decode_wait", 25, 60),
    _ev("sched.sample", 60, 62),
    _ev("sched.emit", 62, 64),
    _ev("ctl.interval", 64, 99),
    _ev("ctl.assign", 65, 95),
    _ev("sched.step", 100, 200, arg=8),
    _ev("model.decode_dispatch", 100, 103),
    _ev("model.decode_wait", 103, 140),
    _ev("mig.apply", 150, 190, arg=12),
    _ev("sched.step", 200, 203, arg=9),
    _ev("ServingEngine.step", 0, 100),            # the harness's own
)


def test_program_spans_by_hand():
    prof = _prof(*STEPS, extra=[_ev("sched.admit", 300, 301, rid=4,
                                    arg=0.75)])
    evs = ps.spans(prof)
    assert [e.name for e in evs][:3] == [
        "sched.step", "sched.admit", "model.prefill_chunk"]
    assert "ServingEngine.step" not in {e.name for e in evs}
    # step 1: 100 - (20 admit + 35 wait + 35 interval) = 10 ms of host
    # work; step 2: 100 - (37 wait + 40 apply) = 23 ms; step 3 has no decode
    assert ps.host_step_seconds(evs) == pytest.approx([0.010, 0.023])
    assert ps.controller_split(evs) == pytest.approx(
        {"ctl.interval": 0.035, "ctl.assign": 0.030})
    assert np.asarray(ps.admissions(evs)) == pytest.approx(
        np.array([(3, 0.001, 0.25, 0.020), (4, 0.300, 0.75, 0.001)]))
    assert [e.dur for e in ps.spans(prof, "ctl.assign")] == pytest.approx(
        [0.030])
    assert ps.base_name("sched.admit#rid=3,arg=0.25#") == "sched.admit"


def test_readers_by_hand():
    prof = _prof(*STEPS)
    assert _read("host_step_ms", prof) == pytest.approx(16.5)
    # Algorithm 1's share of the controller: 30 of 35 ms, whatever the
    # traced seconds
    for window_s in (0.5, 1.0):
        assert _read("assign_share", prof, window_s=window_s) == \
            pytest.approx(100.0 * 30 / 35)


def test_readers_read_nothing_without_the_program_spans():
    """The parent program records no spans: every reader returns None."""
    prof = _prof(_ev("ServingEngine.step", 0, 100),
                 _ev("ServingEngine._interval_plan", 50, 90))
    for name in ("host_step_ms", "assign_share"):
        assert _read(name, prof) is None, name
        run = R.Run(cell={}, conf={}, traffic={}, seconds=45.0)
        assert R.load_module(R.HERE / "metrics" / f"{name}.py").read(run) \
            is None


def test_ring_reduces_like_the_trace():
    """The recorder's ring, read by the span report, gives what the
    readers read from the same spans on the host plane."""
    ring, stack = [], []
    for i, e in enumerate(STEPS[:-1]):
        t0, t1 = e.start_ns * 1e-9, e.end_ns * 1e-9
        while stack and stack[-1][1] <= t0:
            stack.pop()
        st = dict(e.stats)
        ring.append(Span(i, e.name, t0, t1, stack[-1][0] if stack else -1,
                         st.get("rid", -1), st.get("arg", -1)))
        stack.append((i, t1))
    out = SR.ring_summary(ring, (0.0, 1.0), arrival={3: 0.0005})
    assert out["host_step_ms"]["mean"] == pytest.approx(
        _read("host_step_ms", _prof(*STEPS)))
    assert out["assign_of_interval_pct"] == pytest.approx(
        _read("assign_share", _prof(*STEPS)))
    assert out["migration_stall_ms"] == pytest.approx([40.0])
    assert out["queue_wait_s"]["p50"] == pytest.approx(0.25)
    # submitted at t_admit - wait = 0.001 - 0.25, scheduled at 0.0005
    assert out["lateness_s"]["p50"] == pytest.approx(0.001 - 0.25 - 0.0005)
    assert out["host_step_parts_ms"]["model.decode_dispatch"] == \
        pytest.approx(2.5)
    assert SR.ring_summary(ring, (5.0, 6.0), arrival={}) == {}


def test_scope_names():
    assert ps.scope_of("jit(decode_step)/while/body/closed_call/attention/"
                       "kv_write/reshape:") == "decode_step:kv_write"
    assert ps.scope_of("jit(prefill_paged)/while/body/mlp/dot_general:") \
        == "prefill_paged:mlp"
    assert ps.scope_of("jit(decode_step)/div:") == "decode_step:-"
    assert ps.scope_of("") == "(no op_name)"


def test_scope_seconds_cover_the_busy_time():
    """Per-scope self time of the recorded chip trace adds up to its busy
    time, split by program."""
    path = str(FIX / "decode_trace.xplane.pb")
    sec = ps.scope_seconds(path)
    assert sum(sec.values()) == pytest.approx(
        tr.busy_seconds(tr.load(path)), rel=1e-3)
    assert sec["decode_step:-"] > sec["prefill_paged:-"] > 0
    assert ps.scope_seconds(path, device="/device:TPU:9") == {}
    top = ps.top_ops_by_scope(path, k=3)
    assert top[0][0].startswith("%decode_attention_paged_resident")
    assert top[0][1] == "decode_step:-" and top[0][2] > top[1][2]
