"""The plain reference against the program on the CPU at a small size:
the engine's chunked paged prefill, its kernel decode (Pallas in
interpret mode) and its logits after an applied head migration, for a
layout like each configuration's (MHA with LayerNorm and GELU; GQA with
QKV bias, half rotary and SwiGLU)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check as C
from bench import run as R
from bench import weights as W
from bench.tests.tiny import published_pricing, tiny_root


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
