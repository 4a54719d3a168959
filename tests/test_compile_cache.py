"""The persistent compile cache: JAX_COMPILATION_CACHE_DIR wins, else a
fixed directory inside the checkout."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    return calls


def test_env_dir_is_used_and_no_other_is_set(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert compile_cache.enable() == "/cache/from/env"
    assert updates == {}


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
    assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
    assert updates["jax_compilation_cache_dir"] == path
    assert compile_cache.enable() == path  # not derived from pid or time
