import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_env_dir_is_honoured(restore_cache_dir, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


@pytest.mark.parametrize("unset", ["absent", "empty"])
def test_default_is_fixed_checkout_dir(restore_cache_dir, monkeypatch, unset):
    if unset == "absent":
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    # <checkout>/.jax_cache, next to src/ — the same path on every call
    assert (compile_cache.CHECKOUT_CACHE.parent / "src" / "repro").is_dir()
    assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
