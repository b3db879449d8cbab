"""Host-side spans and counters of the program.

The device half of the stack names its work with ``jax.named_scope``
(``stage/<node>``, ``obs/<block>``, ``bytes/shift`` ...); this module is
the host half.  A span is a ``jax.profiler.TraceAnnotation`` that also
adds its seconds to a process-wide counter, so the host work sits in the
same timeline as the device's ops while a profiler records, and is
counted whether one records or not::

    with span("ingress/fill") as extra:
        n = ...                     # the work
        extra["frames"] = n         # further amounts to add

Names in use:

  * ``ingress/fill`` — ``FrameArena.fill``, ``ShardedFrameArena.fill_rss``
    and ``fill_shards``: one span per call, never per frame, counting
    ``frames``;
  * ``compile/*`` — JAX's compile events, added by the listener that
    ``launch.compile_cache.watch_compiles`` registers.

Each counter is a dict of ``calls``, ``seconds`` and whatever else its
spans added.  :func:`counters` returns a snapshot, :func:`reset` clears.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict

_lock = threading.Lock()
_counters: Dict[str, Dict[str, float]] = collections.defaultdict(
    lambda: collections.defaultdict(float))


def add(name: str, **amounts: float) -> None:
    """Add ``amounts`` (``seconds=..., calls=..., frames=...``) to the
    counter ``name``."""
    with _lock:
        c = _counters[name]
        for k, v in amounts.items():
            c[k] += v


@contextlib.contextmanager
def span(name: str):
    """Time the block as one call of ``name``; yields a dict whose
    entries are added to the counter too."""
    import jax
    extra: Dict[str, float] = {}
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        try:
            yield extra
        finally:
            add(name, calls=1, seconds=time.perf_counter() - t0, **extra)


def counters() -> Dict[str, Dict[str, float]]:
    """A snapshot of every counter."""
    with _lock:
        return {k: dict(v) for k, v in _counters.items()}


def reset() -> None:
    with _lock:
        _counters.clear()
