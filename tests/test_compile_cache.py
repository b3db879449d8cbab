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


def test_compiles_are_counted_once_by_kind_and_by_function():
    """``watch_compiles`` adds JAX's compile events to the program's host
    counters: the events no other encloses by kind, every event by
    function name."""
    import jax
    import jax.numpy as jnp

    from repro.obs import host
    compile_cache.watch_compiles()
    compile_cache.watch_compiles()              # registered once

    def counted_step(x):
        return jnp.where(x > 0, x, -x).sum()     # jnp helpers nest traces

    x = jnp.arange(7.0)
    before = host.counters()
    jax.jit(counted_step)(x).block_until_ready()
    after = host.counters()

    def grew(key, field="calls"):
        return after.get(key, {}).get(field, 0) - \
            before.get(key, {}).get(field, 0)

    assert grew("compile/trace/counted_step") == 1
    assert grew("compile/backend/jit(counted_step)") == 1
    assert grew("compile/lower/jit(counted_step)") == 1
    # the helper traces nested in counted_step's count under their own
    # names only: the kind's total grows by the outermost events
    assert grew("compile/trace") == 1
    assert grew("compile/backend") == 1
    outer = grew("compile/trace/counted_step", "seconds")
    assert grew("compile/trace", "seconds") == outer > 0
