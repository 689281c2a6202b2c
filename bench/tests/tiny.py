"""A copy of the benchmark at CPU size, for the tests in this directory.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``,
links the program's ``src/``, and rewrites every configuration to a few
narrow layers (``shrink``) and every traffic mix to short requests,
keeping each configuration's kind of attention (MHA or grouped KV heads,
and each kind of layer its ``layer_types`` names), norm, MLP and bias,
and a routed configuration's expert count, experts per token and window.

``published_pricing(monkeypatch)`` has the harness build the engine with
a controller that prices the published widths (``cost_cfg``), so that the
small copy plans and applies migrations as the full size does.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_MODEL = {"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab_size": 256,
              "dtype": "float32"}
TINY_MHA = {"n_heads": 4, "n_kv_heads": 4, "d_head": 16}
TINY_GQA = {"n_heads": 8, "n_kv_heads": 2, "d_head": 8}
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


def shrink(conf: dict) -> dict:
    """A configuration file's CPU-size copy: ``TINY_MODEL`` widths and
    depth (deep enough to hold each kind of layer of ``layer_types``),
    ``TINY_MHA`` or ``TINY_GQA`` heads, float32, ``TINY_ENGINE``; every
    other key stays as it is (a routed configuration keeps its expert
    count, experts per token and window).  The program is given each key
    that names a field of its ``ModelConfig``."""
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    m = conf["model"]
    heads = TINY_MHA if m["n_kv_heads"] == m["n_heads"] else TINY_GQA
    model = dict(m, **TINY_MODEL, **heads)
    if "layer_types" in m:
        kinds = m["layer_types"]
        depth = max(TINY_MODEL["n_layers"],
                    max(kinds.index(k) for k in set(kinds)) + 1)
        model.update(n_layers=depth, layer_types=kinds[:depth])
    over = {k: v for k, v in model.items() if k in fields}
    over.update(dtype="float32", param_dtype="float32")
    return dict(conf, model=model, overrides=over,
                engine=dict(conf["engine"], **TINY_ENGINE))


def tiny_root(tmp: Path, rate: float = 4.0) -> Path:
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    (tmp / "src").symlink_to(REPO / "src")
    for f in (tmp / "bench" / "configs").glob("*.json"):
        f.write_text(json.dumps(shrink(json.loads(f.read_text()))))
    for f in (tmp / "bench" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t.update(TINY_TRAFFIC)
        a = t["arrivals"]
        t["arrivals"] = dict(a, rate=rate, **({"rate_hi": 3 * rate}
                                              if "rate_hi" in a else {}))
        f.write_text(json.dumps(t))
    return tmp


# a routed configuration the program serves on the paged path today:
# mixtral-8x7b's widths, 8 experts top-2, without its 4096-token window
MIXTRAL = {
    "arch": "mixtral-8x7b", "source": "https://arxiv.org/abs/2401.04088",
    "model": {"n_layers": 32, "d_model": 4096, "n_heads": 32,
              "n_kv_heads": 8, "d_head": 128, "d_ff": 14336,
              "vocab_size": 32000, "norm": "rmsnorm", "norm_eps": 1e-05,
              "mlp": "swiglu", "qkv_bias": False, "rope_theta": 1000000.0,
              "rope_fraction": 1.0, "dtype": "bfloat16", "n_experts": 8,
              "experts_per_token": 2, "sliding_window": 0},
    "overrides": {"sliding_window": 0},
    "reference": "decoder",
    "engine": {"n_slots": 16, "max_seq": 1280, "page_size": 64, "lam": 8,
               "seed": 0},
    "check": {"limits": {"logit_gap": 0.065}}}


def add_cell(root: Path, conf: dict, traffic: str = "chat") -> str:
    """Add ``conf`` at CPU size, and a cell serving it under ``traffic``,
    to the copy at ``root``; returns the cell's name."""
    name = conf["arch"]
    f = f"bench/configs/{name}.json"
    (root / f).write_text(json.dumps(shrink(conf)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": conf["source"],
                             "file": f, "reduced": [], "why": "test"})
    cell = f"{name}.{traffic}"
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell
