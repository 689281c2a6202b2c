"""A copy of the benchmark at CPU size, for the tests in this directory.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``,
links the program's ``src/``, and rewrites every configuration to a few
narrow layers and every traffic mix to short requests, keeping each
configuration's kind of attention, norm, MLP and bias.

``published_pricing(monkeypatch)`` has the harness build the engine with
a controller that prices the published widths (``cost_cfg``), so that the
small copy plans and applies migrations as the full size does.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_MODEL = {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256,
              "dtype": "float32"}
TINY_HEADS = {"musicgen-large": {"n_heads": 4, "n_kv_heads": 4, "d_head": 16},
              "glm4-9b": {"n_heads": 8, "n_kv_heads": 2, "d_head": 8}}
TINY_ENGINE = {"n_slots": 4, "max_seq": 64, "page_size": 16, "lam": 4}
TINY_TRAFFIC = {"prompt_mix": [[0.7, 3, 20], [0.3, 20, 40]],
                "output_mix": [[1.0, 3, 12]], "in_flight": 3,
                "trace_at_s": 0.5, "trace_seconds": 1.0, "check_tokens": 20,
                "straggler": {"device": 0, "slowdown": 20.0}}


def published_pricing(monkeypatch) -> None:
    from repro.configs import get_config

    from bench import run as R
    orig = R.build_engine

    def build(cfg, conf, params_fn, **kw):
        return orig(cfg, conf, params_fn, cost_cfg=get_config(conf["arch"]),
                    **kw)
    monkeypatch.setattr(R, "build_engine", build)


def tiny_root(tmp: Path, rate: float = 4.0) -> Path:
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    (tmp / "src").symlink_to(REPO / "src")
    for f in (tmp / "bench" / "configs").glob("*.json"):
        conf = json.loads(f.read_text())
        model = dict(conf["model"], **TINY_MODEL, **TINY_HEADS[conf["arch"]])
        conf["model"] = model
        conf["overrides"] = {k: model[k] for k in
                             ("n_layers", "d_model", "d_ff", "vocab_size",
                              "n_heads", "n_kv_heads", "d_head")}
        conf["overrides"].update(dtype="float32", param_dtype="float32")
        conf["engine"] = dict(conf["engine"], **TINY_ENGINE)
        f.write_text(json.dumps(conf))
    for f in (tmp / "bench" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t.update(TINY_TRAFFIC)
        a = t["arrivals"]
        t["arrivals"] = dict(a, rate=rate, **({"rate_hi": 3 * rate}
                                              if "rate_hi" in a else {}))
        f.write_text(json.dumps(t))
    return tmp
