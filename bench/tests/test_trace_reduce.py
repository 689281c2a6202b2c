"""``bench/trace_reduce.py`` and the trace metrics against a small trace
recorded on one v5e (``record_trace.py``: a two-layer engine, four
decode steps, one two-chunk prefill) and against hand-made intervals."""
import json
from pathlib import Path

import pytest

from bench import run as R
from bench import trace_reduce as tr

FIX = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def prof():
    return tr.load(str(FIX / "decode_trace.xplane.pb"))


def test_merge_and_self_time_by_hand():
    assert tr.merge([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]
    # a loop event holding two ops: the loop keeps only its own time
    ev = [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 8.0),
          ("c", 11.0, 12.0)]
    assert tr.self_seconds(ev) == {"while": 4.0, "a": 2.0, "b": 4.0,
                                   "c": 1.0}


def test_planes_and_programs(prof):
    rec = json.loads((FIX / "decode_trace.json").read_text())
    assert [p.name for p in tr.device_planes(prof)] == ["/device:TPU:0"]
    assert len(tr.module_seconds(prof, r"^jit_decode_step\b")) \
        == rec["decode_steps"] == 4
    assert len(tr.module_seconds(prof, r"^jit_prefill_paged\b")) \
        == rec["prefill_chunks"] == 2
    # one Pallas call per layer per decode step
    assert len(tr.op_seconds(prof, r"^%decode_attention_paged_resident\b")) \
        == rec["kernel_calls"] == 2 * 4
    assert tr.busy_seconds(prof) == pytest.approx(rec["busy_s"])


def test_busy_and_breakdown_are_consistent(prof):
    busy = tr.busy_seconds(prof)
    ops = tr.line_events(tr.device_planes(prof)[0], tr.OPS_LINE)
    span = max(e for _, _, e in ops) - min(s for _, s, _ in ops)
    assert 0 < busy <= span
    top = tr.top_ops(prof, k=3)
    assert top[0][0].startswith("%decode_attention_paged_resident")
    assert sum(s for _, s in tr.top_ops(prof, k=10 ** 6)) \
        == pytest.approx(busy, rel=0.05)
    steps = tr.host_spans(prof, ["ServingEngine.step"])
    assert len(steps) == 4
    gaps = tr.idle_gaps(prof, steps, k=3)
    assert len(gaps) == 3 and all(g > 0 for _, g in gaps)
    assert {n for n, _ in gaps} <= {"ServingEngine.step", "host"}


def test_metric_readers_on_the_fixture(prof):
    conf = json.loads((R.ROOT / "bench/configs/musicgen-large.json")
                      .read_text())
    conf["model"].update(n_layers=2, d_model=512, n_heads=8, n_kv_heads=8,
                         d_ff=1024)
    peaks = json.loads((R.ROOT / "bench/peaks.json").read_text())
    run = R.Run(cell={}, conf=conf, traffic={}, seconds=1.0,
                peaks=peaks["devices"]["TPU v5 lite"])
    # the most the traced steps can have needed: three slots at 112 tokens
    run.trace = dict(prof=prof, window_s=0.02, decode_ctx=[112] * 12,
                     prompts=[70], chunk=64, n_devices=1)
    m = lambda n: R.load_module(R.ROOT / "bench/metrics" / f"{n}.py").read(run)
    assert m("decode_step_ms") == pytest.approx(
        1e3 * sum(tr.module_seconds(prof, r"^jit_decode_step\b")) / 4)
    assert m("prefill_chunk_ms") > 0
    assert 0 < m("paged_attn_roofline") < 100
    assert 0 < m("mfu") < 100
    assert 0 < m("idle_share") < 100
