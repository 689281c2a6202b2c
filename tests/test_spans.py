"""Span recorder (runtime.spans) threaded through the paged engine and
the interval controller: the model function is untouched, off records
nothing, on nests every span of a step under its ``sched.step``, the
ring is bounded, the page counters agree with outside sampling, and the
in-memory spans agree with the profiler's host plane."""
import gc
import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DeviceNetwork
from repro.runtime import spans as S
from repro.serving.engine import ServingEngine
from tests.conftest import reduced_config

STEP_CHILDREN = {"sched.admit", "kv.mount", "model.decode_dispatch",
                 "model.decode_wait", "sched.sample", "sched.emit",
                 "ctl.interval", "mig.apply"}


def _engine(spans_on: bool, *, maxlen=None, lam=3):
    cfg = reduced_config("llama3-8b", n_layers=3, n_kv_heads=2)
    eng = ServingEngine(cfg, n_slots=2, max_seq=64, lam=lam, seed=0,
                        net=DeviceNetwork.sample(2, seed=1), use_kernel=True,
                        paged=True, page_size=8)
    if maxlen is not None:
        eng.spans = eng.controller.spans = S.SpanRecorder(maxlen=maxlen)
    if spans_on:
        eng.spans.enable()
    return eng


def _serve(eng, lengths=(5, 11, 8, 14, 20), max_new=10, straggle_at=4):
    rng = np.random.default_rng(0)
    for i, n in enumerate(lengths):
        eng.submit(rng.integers(0, 97, size=n).astype(np.int32),
                   max_new_tokens=max_new + (i % 2))
    while True:
        if straggle_at is not None and eng.decode_steps == straggle_at:
            dev = int(eng.controller.head_counts().argmax())
            eng.net.inject_straggler(dev, slowdown=500.0)
        if not eng.step():
            break
    return {r.rid: list(r.out_tokens) for r in eng.finished}


def _by_index(eng):
    return {s.index: s for s in eng.spans.ring}


def _ancestors(sp, idx):
    out = []
    while sp.parent >= 0 and sp.parent in idx:
        sp = idx[sp.parent]
        out.append(sp)
    return out


@pytest.fixture(scope="module")
def served():
    """One engine with spans on, one with them off, same traffic."""
    on, off = _engine(True), _engine(False)
    return on, _serve(on), off, _serve(off)


def test_greedy_streams_identical_with_spans_on_and_off(served):
    on, got, off, want = served
    assert got == want and len(got) == 5
    assert any(m["applied"] and m["n_migrations"] for m in on.migration_log)
    assert on.decode_steps == off.decode_steps
    for k in ("prefill_chunks", "page_mounts", "live_page_steps",
              "pool_page_steps"):
        assert getattr(on, k) == getattr(off, k) > 0, k


def test_off_records_nothing_and_allocates_no_span(monkeypatch):
    def no_span(*a, **k):
        raise AssertionError("a span was allocated with the recorder off")
    monkeypatch.setattr(S._Open, "__init__", no_span)
    eng = _engine(False)
    hooks = set(map(id, eng.spans._hooks))
    _serve(eng)
    assert not eng.spans.ring and not eng.spans.active
    assert eng.spans.span("sched.emit") is S.OFF
    assert not hooks & set(map(id, gc.callbacks))


def test_each_step_nests_its_spans_under_one_sched_step(served):
    on = served[0]
    idx = _by_index(on)
    steps = on.spans.spans("sched.step")
    assert [s.arg for s in steps] == sorted(s.arg for s in steps)
    waits = on.spans.spans("model.decode_wait")
    assert len(waits) == on.decode_steps
    step_of = {}
    for sp in on.spans.ring:
        if sp.name == "sched.step":
            assert sp.parent == -1
            continue
        if sp.name.startswith("host."):
            continue
        anc = _ancestors(sp, idx)
        assert anc and anc[-1].name == "sched.step", sp
        for a in anc:                          # nested in time as well
            assert a.t0 <= sp.t0 <= sp.t1 <= a.t1, (sp, a)
        if sp.name in STEP_CHILDREN and sp.name != "kv.mount":
            assert idx[sp.parent].name == "sched.step", sp
        step_of.setdefault(anc[-1].index, []).append(sp.name)
    for names in step_of.values():
        if "model.decode_wait" in names:
            for n in ("model.decode_dispatch", "model.decode_wait",
                      "sched.sample", "sched.emit"):
                assert names.count(n) == 1, names
    # the controller's parts sit under its interval, the permute under apply
    for sp in on.spans.ring:
        if sp.name in ("ctl.observe", "ctl.assign", "ctl.payback",
                       "ctl.perms", "ctl.estimate"):
            assert idx[sp.parent].name == "ctl.interval"
        if sp.name in ("mig.permute", "mig.head_rows"):
            assert idx[sp.parent].name == "mig.apply"
    n_int = len(on.migration_log)
    for n in ("ctl.interval", "ctl.observe", "ctl.assign", "ctl.payback",
              "ctl.perms", "ctl.estimate", "mig.apply", "mig.head_rows"):
        assert len(on.spans.spans(n)) == n_int, n
    applied = [s for s in on.spans.spans("mig.apply") if s.arg > 0]
    assert applied and len(on.spans.spans("mig.permute")) == len(applied)
    # plan_s is the ctl.interval span's own duration
    plan_s = [m["plan_s"] for m in on.migration_log]
    iv = [s.t1 - s.t0 for s in on.spans.spans("ctl.interval")]
    assert plan_s == iv


def test_admission_spans_carry_the_request(served):
    on = served[0]
    idx = _by_index(on)
    admits = on.spans.spans("sched.admit")
    reqs = {r.rid: r for r in on.finished}
    assert sorted(s.rid for s in admits) == sorted(reqs)
    chunks = on.spans.spans("model.prefill_chunk")
    for a in admits:
        r = reqs[a.rid]
        assert r.t_submit <= r.t_admit <= r.t_first
        assert a.t0 == r.t_admit and a.arg == r.t_admit - r.t_submit
        mine = [c for c in chunks if c.rid == a.rid]
        assert len(mine) == math.ceil(len(r.prompt) / on.prefill_chunk)
        assert [c.arg for c in mine] == list(
            range(0, len(r.prompt), on.prefill_chunk))
        assert all(c.parent == a.index for c in mine)
        first = [s for s in on.spans.spans("sched.first_token")
                 if s.rid == a.rid]
        assert len(first) == 1 and first[0].parent == a.index
        assert first[0].t0 == r.t_first
        assert idx[a.parent].name == "sched.step"
    assert on.prefill_chunks == len(chunks)
    assert on.page_mounts == len(on.spans.spans("kv.mount"))


def test_ring_is_bounded():
    eng = _engine(True, maxlen=16)
    _serve(eng, lengths=(5, 11), max_new=6, straggle_at=None)
    ring = list(eng.spans.ring)
    assert len(ring) == 16 and eng.spans._next > 64
    # records are kept in the order they closed: the newest sixteen
    assert ring[-1].name == "sched.step" and ring[-1].parent == -1
    assert min(s.index for s in ring) > eng.spans._next - 40


def test_live_page_counters_match_sampling_at_dispatch():
    """``live_page_steps / pool_page_steps`` is the mean share that
    wrapping ``_decode_jit`` from outside samples (``kv_live_share``)."""
    eng = _engine(False)
    total = sum(a.n_pages for a in eng.allocators)
    seen = []
    orig = eng._decode_jit

    def decode(*a):
        seen.append(sum(al.live_pages for al in eng.allocators) / total)
        return orig(*a)
    eng._decode_jit = decode
    _serve(eng)
    assert len(seen) == eng.decode_steps
    assert eng.live_page_steps / eng.pool_page_steps == pytest.approx(
        float(np.mean(seen)), rel=1e-12)


def test_host_pauses_are_recorded():
    rec = S.SpanRecorder()
    rec.enable()
    gc.collect()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + jnp.float32(0.125))
    f(jnp.arange(7.0)).block_until_ready()
    rec.disable()
    gcs = rec.spans("host.gc")
    assert any(g.arg == 2 for g in gcs)     # the full collection above
    assert all(g.t0 <= g.t1 for g in gcs)
    comp = rec.spans("host.compile")
    assert comp and all(c.t0 <= c.t1 for c in comp)
    assert any("lambda" in c.arg for c in comp)
    n = len(rec.ring)
    gc.collect()                      # off again: nothing more recorded
    assert len(rec.ring) == n and not rec.active


def test_profiler_host_plane_matches_ring(tmp_path):
    """A profiler trace switches the recorder on by itself (from the next
    step), and every in-memory span of the traced steps sits on the host
    plane under its name, in the same order, with its duration."""
    from jax.profiler import ProfileData
    eng = _engine(False)
    rng = np.random.default_rng(1)
    for n in (9, 17):
        eng.submit(rng.integers(0, 97, size=n).astype(np.int32),
                   max_new_tokens=8)
    for _ in range(3):
        eng.step()                                  # compile outside
    assert not eng.spans.ring
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(4):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    eng.step()                               # the recorder follows: off
    assert not eng.spans.active
    mine = [s for s in eng.spans.ring if not s.name.startswith("host.")]
    assert len([s for s in mine if s.name == "sched.step"]) == 4
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    prof = ProfileData.from_file(path)
    names = {s.name for s in mine}
    host = sorted(((e.name.split("#")[0], e.start_ns, e.duration_ns,
                    dict(e.stats))
                   for p in prof.planes if p.name == "/host:CPU"
                   for line in p.lines for e in line.events
                   if e.name.split("#")[0] in names),
                  key=lambda x: (x[1], -x[2]))
    ring = sorted(mine, key=lambda s: (s.t0, -(s.t1 - s.t0), s.index))
    assert [h[0] for h in host] == [s.name for s in ring]
    for h, s in zip(host, ring):
        assert abs(h[2] * 1e-9 - (s.t1 - s.t0)) < 5e-4, (h, s)
        if s.rid >= 0:
            assert h[3].get("rid") == s.rid
