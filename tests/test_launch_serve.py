"""The serve entry point and where entry points keep the compile cache."""
import pathlib

import jax

from repro.launch import serve
from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
from repro.serving.engine import ServingEngine

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_serve_main_returns_the_engine_it_served(monkeypatch, tmp_path):
    """``main`` hands back its engine: every request done, the straggler
    slowed only after the first interval, and each interval's controller
    host time logged."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    eng = serve.main(["--reduced", "--paged", "--page-size", "8",
                      "--requests", "3", "--tokens", "6", "--lam", "2",
                      "--mixed-lengths", "--min-prompt-len", "3",
                      "--prompt-len", "12", "--max-seq", "32",
                      "--straggler", "0"])
    assert isinstance(eng, ServingEngine)
    assert eng.max_seq == 32
    assert sorted(len(r.out_tokens) for r in eng.finished) == [6, 6, 6]
    assert all(3 <= len(r.prompt) <= 12 for r in eng.finished)
    assert eng.token_sink is None            # the one-shot hook fired
    assert eng.net._pinned_load[0] > 0
    assert all(m["plan_s"] > 0 and m["infeasible"] is False
               for m in eng.migration_log)


def test_compile_cache_leaves_an_environment_dir_to_jax(monkeypatch,
                                                        tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_git_ignored_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
