"""The persistent compilation cache's directory: the deployment's
``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed in-checkout path."""
from pathlib import Path

from repro.launch.compile_cache import compile_cache_dir

CHECKOUT_CACHE = str(Path(__file__).resolve().parents[1] / ".jax_compile_cache")


def test_env_var_set_leaves_jax_untouched(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache_dir() is None


def test_env_var_unset_uses_the_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache_dir() == CHECKOUT_CACHE


def test_same_dir_from_any_working_directory(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    first = compile_cache_dir()
    (tmp_path / "elsewhere").mkdir()
    monkeypatch.chdir(tmp_path / "elsewhere")
    assert compile_cache_dir() == first == CHECKOUT_CACHE
