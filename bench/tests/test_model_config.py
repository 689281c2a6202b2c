"""``bench/run.py``'s ``model_config``: the file's model block against the
program's ``ModelConfig``."""
import copy
import json

import pytest

from bench import check as C
from bench import run as R
from bench.tests.tiny import MIXTRAL, shrink

GLM = json.loads((R.ROOT / "bench/configs/glm4-9b.json").read_text())


@pytest.mark.parametrize("conf", [GLM, MIXTRAL, shrink(MIXTRAL)],
                         ids=["glm4-9b", "mixtral", "mixtral-tiny"])
def test_files_the_program_runs_pass(conf):
    cfg = R.model_config(conf)
    assert cfg.n_experts == conf["model"].get("n_experts", 0)


@pytest.mark.parametrize("key,value", [
    ("n_experts", 16), ("experts_per_token", 1), ("sliding_window", 512),
    ("layer_types", ["window"] * 32), ("d_ff", 4096)])
def test_a_disagreement_is_refused(key, value):
    conf = copy.deepcopy(MIXTRAL)
    conf["model"][key] = value
    with pytest.raises(SystemExit, match=key):
        R.model_config(conf)


def test_an_absent_key_means_its_default():
    # the program routes experts; a file that states none is dense
    conf = copy.deepcopy(MIXTRAL)
    del conf["model"]["n_experts"]
    with pytest.raises(SystemExit, match="n_experts"):
        R.model_config(conf)


def test_an_unknown_key_is_refused_unless_the_reference_reads_it(
        monkeypatch):
    conf = copy.deepcopy(GLM)
    conf["model"]["rope_window_theta"] = 10000.0
    with pytest.raises(SystemExit, match="rope_window_theta"):
        R.model_config(conf)
    ref = C._reference_module("decoder")
    monkeypatch.setattr(ref, "SPEC_KEYS", ("rope_window_theta",))
    monkeypatch.setattr(C, "_reference_module", lambda name: ref)
    R.model_config(conf)


def test_a_field_the_program_gains_is_compared():
    """A key that names any field of ModelConfig is held to it, with no
    list in the harness to extend: ``kv_quant`` is one."""
    conf = copy.deepcopy(GLM)
    conf["model"]["kv_quant"] = True
    with pytest.raises(SystemExit, match="kv_quant"):
        R.model_config(conf)
    conf["model"]["kv_quant"] = False
    R.model_config(conf)
