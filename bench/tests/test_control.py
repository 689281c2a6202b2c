"""The control fails a limit at a size a test run can hold: the plain
reference computed on fp8 operands (a step below the bfloat16 served)
picks tokens whose float32 logits lie further below the best than one of
the configuration's limits allows; with routed experts too, whose
projections the control runs on fp8 operands as well."""
import copy
import json

import numpy as np
import pytest

from bench import check as C
from bench import run as R
from bench.tests.tiny import MIXTRAL

SMALL = {"musicgen-large": dict(n_layers=8, d_model=256, d_ff=1024,
                                n_heads=4, n_kv_heads=4, d_head=64,
                                vocab_size=2048),
         "glm4-9b": dict(n_layers=4, d_model=256, d_ff=768, n_heads=8,
                         n_kv_heads=2, d_head=32, vocab_size=8192),
         "mixtral-8x7b": dict(n_layers=4, d_model=256, d_ff=512, n_heads=8,
                              n_kv_heads=2, d_head=32, vocab_size=8192)}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_is_not_correct(name):
    conf = copy.deepcopy(MIXTRAL) if name == MIXTRAL["arch"] else \
        json.loads((R.ROOT / "bench/configs" / f"{name}.json").read_text())
    conf["model"].update(SMALL[name])
    V = SMALL[name]["vocab_size"]
    rng = np.random.default_rng(0)
    seqs = [(rng.integers(0, V, 100), list(rng.integers(0, V, 150)))
            for _ in range(2)]
    res = C.gaps(conf, 3, seqs, quant="fp8")
    correct, checks = C.judge(conf["check"]["limits"], res, "fp8")
    assert not correct, checks
