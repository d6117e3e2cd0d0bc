"""The compile-cache location helper: ``JAX_COMPILATION_CACHE_DIR`` wins,
otherwise a fixed ``.jax_cache/`` at the checkout root."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_else_is_set(
    monkeypatch, tmp_path, restore_cache_dir
):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("env", [None, ""], ids=["unset", "empty"])
def test_fixed_checkout_path_otherwise(monkeypatch, restore_cache_dir, env):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CHECKOUT_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert (compile_cache.CHECKOUT_ROOT / "src" / "repro").is_dir()
