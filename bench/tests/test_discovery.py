"""Adding a traffic mix or a per-layer metric takes new files only: a
copy of the benchmark gains a mix file, a metric reader and their entries
in BENCHMARK.json, and the harness finds both by name."""
import json
import time

from bench import run as R
from bench.tests.tiny import published_pricing, tiny_root

METRIC = '''"""Requests still queued when the window closed."""


def read(run):
    return run.counters["queue_at_close"]
'''


def test_new_traffic_and_metric_files_are_picked_up(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    published_pricing(monkeypatch)
    root = tiny_root(tmp_path)
    chat = json.loads((root / "bench/traffic/chat.json").read_text())
    mix = dict(chat, arrivals={"process": "poisson", "rate": 9.0},
               schedule_seed=3)
    (root / "bench/traffic/chat-new.json").write_text(json.dumps(mix))
    (root / "bench/metrics/queue_at_close.py").write_text(METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "glm4-9b.chat-new",
                               "config": "glm4-9b", "traffic": "chat-new",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queue_at_close", "unit": "requests",
                               "better": "lower", "source": "program_counter",
                               "layer": "scheduler", "moves": "itl_p95_ms",
                               "workloads": ["glm4-9b.chat-new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = R.run_cell(root, "glm4-9b.chat-new", 11, 2.0, True,
                     require_accelerator=False, t_process=time.monotonic(),
                     check=False, log=lambda *a, **k: None).result
    assert "queue_at_close" in out["metrics"]
    assert out["metrics"]["queue_at_close"]["unit"] == "requests"
    # the new file's arrivals, not chat's: more than chat's rate offers
    from bench import traffic as T
    assert len(T.schedule(chat, 2.0, 11, 256)) < out["attempted"] \
        <= len(T.schedule(mix, 2.0, 11, 256))
