"""The comparison that decides ``correct`` sees a broken timed path.

Each case drives a whole run of a cell on the CPU at a small size (the
harness's look for a chip is skipped) with the engine broken underneath,
and checks that ``correct`` comes out false; the sound run comes out
true.  Every cell runs on one chip, so there is no exchange between chips
to leave out."""
import time

import jax
import numpy as np
import pytest

from bench import run as R
from bench.tests.tiny import published_pricing, tiny_root


def state_unchanged(eng):
    """A decode step that returns its state unchanged."""
    step = jax.jit(eng.model.decode_step)
    eng._decode_jit = lambda p, s, t: (step(p, s, t)[0], s)


def token_altered(eng):
    """The first slot's token altered where the sampler produces it."""
    orig = eng._sample

    def sample(logits):
        toks = np.array(orig(logits))
        if toks.shape[0] > 1:
            toks[0] = (toks[0] + 1) % eng.cfg.vocab_size
        return toks
    eng._sample = sample


def half_batch(eng):
    """Half of the slots left out of the step: their rows' logits are the
    other half's."""
    orig = eng._decode_jit

    def decode(p, s, t):
        logits, st = orig(p, s, t)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:logits.shape[0] - h]), st
    eng._decode_jit = decode


@pytest.mark.parametrize("cell", ["musicgen-large.audio-decode",
                                  "glm4-9b.chat"])
@pytest.mark.parametrize("fault", [None, state_unchanged, token_altered,
                                   half_batch],
                         ids=["sound", "state_unchanged", "token_altered",
                              "half_batch"])
def test_fault_makes_run_incorrect(fault, cell, tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    published_pricing(monkeypatch)
    root = tiny_root(tmp_path)
    out = R.run_cell(root, cell, 2 ** 31 + 3, 3.0, False,
                     require_accelerator=False, t_process=time.monotonic(),
                     window_hook=fault, log=lambda *a, **k: None).result
    assert out["correct"] is (fault is None), out["check"]
