"""The persistent compilation cache: where it goes, and that CPU runs
(the tests) keep none."""
from pathlib import Path

import jax

from repro import compile_cache


def test_cache_dir_is_fixed_at_the_checkout():
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.CACHE_DIR == root / ".jax_cache"


def test_cpu_runs_keep_no_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_accelerator_runs_use_the_env_dir_or_the_checkout(monkeypatch,
                                                         tmp_path):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before   # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == str(
            compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
