"""Where the chip entry points keep JAX's persistent compilation cache."""
import os

from repro.launch import compile_cache


def test_cache_dir_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first, second = compile_cache.cache_dir(), compile_cache.cache_dir()
    assert first == second
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(repo, ".jax_cache")
