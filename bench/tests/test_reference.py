"""The plain reference against the program on the CPU at a small size:
the engine's chunked paged prefill, its kernel decode (Pallas in
interpret mode) and its logits after an applied head migration, for a
layout like each configuration's (MHA with LayerNorm and GELU; GQA with
QKV bias, half rotary and SwiGLU), and for routed experts after an
applied expert migration; and the reference's window layers."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check as C
from bench import run as R
from bench import weights as W
from bench.tests.tiny import (MIXTRAL, add_cell, published_pricing, shrink,
                              tiny_root)

GLM = json.loads((R.ROOT / "bench/configs/glm4-9b.json").read_text())


def test_vmapped_weights_equal_per_layer_draws():
    spec = dict(json.loads((R.ROOT / "bench" / "configs" / "glm4-9b.json")
                           .read_text())["model"],
                n_layers=3, d_model=32, d_ff=48, vocab_size=64,
                n_heads=4, n_kv_heads=2, d_head=8)
    root = W.root_key(2 ** 33 + 5)
    full = W.make_params(root, spec)
    for l in range(3):
        one = W.layer_weights(W.layer_key(root, l), spec)
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda x: x[l],
                                                     full["layers"]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("cell", ["musicgen-large.audio-decode",
                                  "glm4-9b.chat"])
def test_reference_matches_engine(cell, tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    published_pricing(monkeypatch)
    root = tiny_root(tmp_path)
    _, c, conf, traffic = R.load_cell(root, cell)
    seed = 2 ** 31 + 77
    eng = R.build_engine(R.model_config(conf), conf,
                         lambda: W.make_params(W.root_key(seed),
                                               conf["model"]))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, size=n).astype(np.int32)
               for n in (37, 9, 21, 3, 30)]
    for p in prompts:
        eng.submit(p, max_new_tokens=10)
    slowed = []
    while eng.step():
        if eng.migration_log and not slowed:
            eng.net.inject_straggler(0, slowdown=20.0)
            slowed.append(eng.decode_steps)
    applied = [m for m in eng.migration_log if m["applied"]]
    assert applied, "the controller applied no plan: no migration covered"
    first_applied = applied[0]["step"]
    done = sorted(eng.finished, key=lambda r: r.rid)
    assert len(done) == 5
    # tokens after the first applied migration are part of the comparison
    assert eng.decode_steps > first_applied
    seqs = [(r.prompt, r.out_tokens) for r in done]
    res = C.gaps(conf, seed, seqs, pad_to=16)
    # float32 program against float32 reference: the served token is the
    # reference's argmax at every position, to rounding
    assert res["tokens"] == 50
    assert res["gap"] < 1e-4, res


def test_prefill_logits_equal_reference(tmp_path):
    root = tiny_root(tmp_path)
    _, _, conf, _ = R.load_cell(root, "glm4-9b.chat")
    seed = 5
    spec = conf["model"]
    eng = R.build_engine(R.model_config(conf), conf,
                         lambda: W.make_params(W.root_key(seed), spec))
    prompt = np.random.default_rng(1).integers(0, 256, size=40)
    eng.submit(prompt, max_new_tokens=2)
    logits = []
    orig = eng._paged_prefill_jit

    def spy(*a):
        lg, st = orig(*a)
        logits.append(np.asarray(lg[0]))
        return lg, st
    eng._paged_prefill_jit = spy
    eng.step()
    ref = C._reference_module(conf["reference"])
    root_key = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        outer = W.outer_weights(W.outer_key(root_key), spec)
        x = ref.embed(outer, jnp.asarray(prompt))
        for l in range(spec["n_layers"]):
            x = ref.layer(spec, W.layer_weights(W.layer_key(root_key, l),
                                                spec), x)
        want = np.asarray(ref.logits(spec, outer, x))[-1]
    # 40 tokens in 16-token chunks: the last chunk's logits
    assert len(logits) == 3
    np.testing.assert_allclose(logits[-1], want, rtol=1e-4, atol=1e-4)


def _window_spec(window: int, kinds=None) -> dict:
    spec = dict(GLM["model"], n_layers=2, d_model=32, d_ff=48, vocab_size=64,
                n_heads=4, n_kv_heads=2, d_head=8, dtype="float32",
                sliding_window=window)
    if kinds:
        spec["layer_types"] = kinds
    return spec


@pytest.mark.parametrize("window", [1, 5])
def test_window_mask(window):
    ref = C._reference_module("decoder")
    m = np.asarray(ref._mask(8, window))
    i, j = np.indices((8, 8))
    np.testing.assert_array_equal(m, (j <= i) & (j > i - window))
    np.testing.assert_array_equal(np.asarray(ref._mask(8)), j <= i)


def test_window_wider_than_sequence_is_full_attention():
    ref = C._reference_module("decoder")
    spec = _window_spec(12)
    p = W.layer_weights(W.layer_key(W.root_key(4), 0), spec)
    x = jax.random.normal(jax.random.PRNGKey(0), (12, 32))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(
            np.asarray(ref.layer(spec, p, x, kind="window")),
            np.asarray(ref.layer(spec, p, x, kind="full")))


def test_window_one_attends_to_self_only():
    """With a window of 1 a position sees its own key alone, so the layer
    does not depend on the queries (zeroed here); the first position,
    which sees only itself anyway, reads as under full attention."""
    ref = C._reference_module("decoder")
    spec = _window_spec(1)
    p = W.layer_weights(W.layer_key(W.root_key(4), 0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 32))
    no_q = dict(p, attn=dict(p["attn"], wq=p["attn"]["wq"] * 0,
                             bq=p["attn"]["bq"] * 0))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.layer(spec, p, x, kind="window"))
        blind = np.asarray(ref.layer(spec, no_q, x, kind="window"))
        full = np.asarray(ref.layer(spec, p, x, kind="full"))
    np.testing.assert_allclose(got, blind, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got[0], full[0])
    assert not np.allclose(got[1:], full[1:])


def test_gaps_runs_each_layer_with_its_kind():
    """``check.gaps`` over layers of two kinds reads the gaps a forward
    written out by hand, layer by layer with each layer's kind, gives."""
    ref = C._reference_module("decoder")
    kinds = ["window", "full", "window"]
    spec = _window_spec(4, kinds)
    spec["n_layers"] = 3
    conf = {"reference": "decoder", "model": spec}
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, 64, 9), list(rng.integers(0, 64, 7)))]
    res = C.gaps(conf, 21, seqs, pad_to=16)
    root = W.root_key(21)
    tok = np.concatenate([seqs[0][0], seqs[0][1]])
    with jax.default_matmul_precision("highest"):
        outer = W.outer_weights(W.outer_key(root), spec)
        x = ref.embed(outer, jnp.asarray(tok))
        for l, kd in enumerate(kinds):
            p = jax.jit(lambda r, l=l: W.layer_weights(W.layer_key(r, l),
                                                       spec))(root)
            x = ref.layer(spec, p, x, kind=kd)
        lg = np.asarray(ref.logits(spec, outer, x))
    pos = np.arange(len(seqs[0][0]) - 1, len(tok) - 1)
    want = lg[pos].max(-1) - lg[pos, tok[pos + 1]]
    np.testing.assert_allclose(res["gap"], want.max(), rtol=1e-5, atol=1e-5)
    # the window changes the answer: all-full layers read other gaps
    full = C.gaps({"reference": "decoder",
                   "model": dict(spec, layer_types=["full"] * 3)},
                  21, seqs, pad_to=16)
    assert full["per_request"] != res["per_request"]


def test_routed_cell_is_correct(tmp_path, monkeypatch):
    """A tiny mixtral (8 experts, top-2, no window) served through the
    whole run: the engine's routed experts, after an applied expert
    migration, read no gap against the float32 reference.  Priced at its
    own size, the controller moves experts (at the published widths it
    moves nothing on this copy)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    root = tiny_root(tmp_path)
    cell = add_cell(root, MIXTRAL)
    engines = []
    run = R.run_cell(root, cell, 2 ** 31 + 3, 3.0, False,
                     require_accelerator=False, t_process=time.monotonic(),
                     window_hook=engines.append, log=lambda *a, **k: None)
    out = run.result
    assert out["correct"] is True, out["check"]
    assert run.gaps["tokens"] >= 20
    assert run.gaps["gap"] < 1e-4, run.gaps
    log = engines[0].migration_log
    # set-up waited for the expert migration; no head moved
    assert any(m["expert_applied"] for m in log)
    assert not any(m["applied"] for m in log)


def test_routed_prefill_logits_equal_reference(tmp_path):
    conf = shrink(MIXTRAL)
    spec = conf["model"]
    seed = 6
    eng = R.build_engine(R.model_config(conf), conf,
                         lambda: W.make_params(W.root_key(seed), spec))
    prompt = np.random.default_rng(2).integers(0, 256, size=40)
    eng.submit(prompt, max_new_tokens=2)
    logits = []
    orig = eng._paged_prefill_jit

    def spy(*a):
        lg, st = orig(*a)
        logits.append(np.asarray(lg[0]))
        return lg, st
    eng._paged_prefill_jit = spy
    eng.step()
    ref = C._reference_module(conf["reference"])
    root_key = W.root_key(seed)
    with jax.default_matmul_precision("highest"):
        outer = W.outer_weights(W.outer_key(root_key), spec)
        x = ref.embed(outer, jnp.asarray(prompt))
        for l in range(spec["n_layers"]):
            x = ref.layer(spec, W.layer_weights(W.layer_key(root_key, l),
                                                spec), x)
        want = np.asarray(ref.logits(spec, outer, x))[-1]
    assert len(logits) == 3
    np.testing.assert_allclose(logits[-1], want, rtol=1e-4, atol=1e-4)
