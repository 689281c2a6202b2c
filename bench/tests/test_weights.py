"""``bench/weights.py``: a dense spec draws the bits it always drew, and a
routed spec gets the program's ``moe`` layout, leaf for leaf."""
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import run as R
from bench import weights as W
from bench.tests.tiny import MIXTRAL, shrink

SMALL = dict(n_layers=2, d_model=32, d_ff=48, vocab_size=64, n_heads=4,
             d_head=8)
KV = {"glm4-9b": 2, "musicgen-large": 4}
# sha256 (first 16 hex digits) of every leaf of make_params(root_key(2**33
# + 5), SMALL spec) in bfloat16, recorded before routed experts existed
DENSE = {
    "glm4-9b": {
        "['layers']['attn']['bk']": "8f83c42fe204b6bd",
        "['layers']['attn']['bq']": "e870f463fb75d1c2",
        "['layers']['attn']['bv']": "fe90138803ffec6d",
        "['layers']['attn']['wk']": "cea6176d3d67dfb0",
        "['layers']['attn']['wo']": "91f53d3e5cb5be9f",
        "['layers']['attn']['wq']": "f29a97c10026a77a",
        "['layers']['attn']['wv']": "196a46f1a761a494",
        "['layers']['ln1']": "74fcb0dc5e1f0491",
        "['layers']['ln2']": "7e5428883126c6b3",
        "['layers']['mlp']['w_down']": "92ac8edc0a9df8a5",
        "['layers']['mlp']['w_gate']": "1fdcfc1a9d6139dc",
        "['layers']['mlp']['w_up']": "f82c30d06d14bcad",
        "['lm_head']": "a5bacf9de7cb9983",
        "['ln_f']": "f6b9b94c9db6d2f7",
        "['tok_embed']": "49a3e052df3cc908"},
    "musicgen-large": {
        "['layers']['attn']['wk']": "32d599533ac86b81",
        "['layers']['attn']['wo']": "91f53d3e5cb5be9f",
        "['layers']['attn']['wq']": "f29a97c10026a77a",
        "['layers']['attn']['wv']": "8b9b329cf9807056",
        "['layers']['ln1']": "74fcb0dc5e1f0491",
        "['layers']['ln1_b']": "a3f41fb6b8948b6a",
        "['layers']['ln2']": "7e5428883126c6b3",
        "['layers']['ln2_b']": "f19423469cfe4d78",
        "['layers']['mlp']['b_down']": "f580687630f3fb52",
        "['layers']['mlp']['b_up']": "5bdd685a8e20c9d1",
        "['layers']['mlp']['w_down']": "92ac8edc0a9df8a5",
        "['layers']['mlp']['w_up']": "1fdcfc1a9d6139dc",
        "['lm_head']": "a5bacf9de7cb9983",
        "['ln_f']": "f6b9b94c9db6d2f7",
        "['ln_f_b']": "8dffcc76dbd5c8af",
        "['tok_embed']": "49a3e052df3cc908"}}


def leaf_hashes(params) -> dict:
    return {jax.tree_util.keystr(k):
            hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
            for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_leaves_keep_their_bits(name):
    spec = dict(json.loads((R.ROOT / "bench/configs" / f"{name}.json")
                           .read_text())["model"],
                n_kv_heads=KV[name], **SMALL)
    assert leaf_hashes(W.make_params(W.root_key(2 ** 33 + 5), spec)) \
        == DENSE[name]


def test_routed_tree_is_the_programs():
    from repro.models.transformer import TransformerLM
    conf = shrink(MIXTRAL)
    cfg = R.model_config(conf)
    want = jax.eval_shape(TransformerLM(cfg).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: W.make_params(W.root_key(3), conf["model"]))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    assert got["layers"]["moe"]["router"].dtype == np.float32


def test_routed_leaves_drawn_per_layer():
    spec = shrink(MIXTRAL)["model"]
    root = W.root_key(2 ** 33 + 9)
    full = W.make_params(root, spec)["layers"]["moe"]
    # jitted, as bench/check.py regenerates a layer (eager draws may round
    # a float32 product differently)
    layer_w = jax.jit(lambda r, l: W.layer_weights(W.layer_key(r, l), spec))
    for l in range(spec["n_layers"]):
        one = layer_w(root, l)["moe"]
        for k in one:
            np.testing.assert_array_equal(np.asarray(one[k]),
                                          np.asarray(full[k][l]))
    # the experts and layers differ from one another
    w = np.asarray(full["w_gate"])
    assert not np.array_equal(w[0, 0], w[0, 1])
    assert not np.array_equal(w[0], w[1])
