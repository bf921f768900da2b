"""Process-level set-up shared by the entry points (launch/runtime.py)."""

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import runtime


def _with_cache_dir_restored(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn(), jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path, configured = _with_cache_dir_restored(runtime.enable_compile_cache)
    assert path == str(runtime.CHECKOUT / ".jax_cache") == configured
    assert (runtime.CHECKOUT / "chip_smoke.py").is_file()


def test_compile_cache_env_wins_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    path, configured = _with_cache_dir_restored(runtime.enable_compile_cache)
    assert path == str(tmp_path)
    assert configured == before
