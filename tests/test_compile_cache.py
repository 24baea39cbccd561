"""The persistent compilation cache goes to one fixed place."""
import jax

from repro.launch import compile_cache


def _restore(prev):
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        _restore(prev)


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
        root = compile_cache.CACHE_DIR.parent
        assert (root / "pyproject.toml").exists()
        assert compile_cache.CACHE_DIR.name == ".jax_cache"
    finally:
        _restore(prev)
