"""JAX's persistent compilation cache for the entry points that run on a
chip (``chip_smoke.py``, the benchmarks); the tests do not use it.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads it itself, so nothing else is set in code), and otherwise a fixed
path inside the checkout: the path is part of the cache key, so it never
depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import threading

from repro.obs import host

ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def cache_dir() -> str:
    """Where compiled programs are kept."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process,
    however short, and count the compiles (:func:`watch_compiles`);
    returns the cache's directory.

    The cache key takes in the programs' metadata: the named scopes
    (``stage/<node>``, ``obs/*``, ``bytes/*``) live there, and by default
    JAX would serve a program compiled with other scopes, or none, under
    the same key, whose profile then names the wrong stages."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    watch_compiles()
    return path


# JAX's compile events (jax._src.dispatch) and the counter each feeds.
# JAX records each event twice: a scalar as it opens and a duration as it
# closes, so the listeners see which events nest in others (an eager op's
# compile inside a trace, a jitted helper's trace inside its caller's).
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower",
    "/jax/core/compile/backend_compile_duration": "compile/backend",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_open = threading.local()
_watching = False


def _on_open(event: str, value: float, **kw) -> None:
    if event in COMPILE_EVENTS:
        _open.n = getattr(_open, "n", 0) + 1


def _on_close(event: str, duration: float, **kw) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is None:
        return
    depth = getattr(_open, "n", 0)
    _open.n = max(depth - 1, 0)
    if depth <= 1:                     # outermost: no compile event around
        host.add(name, calls=1, seconds=duration)
    fun = kw.get("fun_name")
    if fun:
        host.add(f"{name}/{fun}", calls=1, seconds=duration)


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        host.add("compile/cache_hit", calls=1)


def watch_compiles() -> None:
    """From now on, count this process's compiles in the program's host
    counters (``repro.obs.host``).  ``compile/trace``, ``compile/lower``
    and ``compile/backend`` (a compile, or a read from the persistent
    cache) add the ``calls`` and ``seconds`` of the events that no other
    compile event encloses, so their seconds add up to the time spent
    compiling; ``compile/<kind>/<fun_name>`` counts every event of that
    function, nested or not; ``compile/cache_hit`` counts reads from the
    persistent cache.  Registered once per process."""
    global _watching
    import jax.monitoring
    if _watching:
        return
    _watching = True
    jax.monitoring.register_scalar_listener(_on_open)
    jax.monitoring.register_event_duration_secs_listener(_on_close)
    jax.monitoring.register_event_listener(_on_event)
