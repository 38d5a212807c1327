"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro import runtime


@pytest.fixture
def cache_dir_config():
    """Restore the process-wide cache setting whatever the test does."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_defaults_to_fixed_dir_in_checkout(monkeypatch,
                                                 cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.use_compile_cache()
    assert first == runtime.use_compile_cache() == str(runtime.DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert (runtime.DEFAULT_CACHE_DIR.parent / "pyproject.toml").is_file()


def test_cache_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                               cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert runtime.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
