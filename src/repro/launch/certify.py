"""Zero-host-transfer certification of a compiled path.

The direct-attached claim is that the scanned region never touches the
host.  :func:`assert_no_host_callbacks` checks it on the jaxpr, sub-jaxprs
(scan and cond bodies, Pallas kernels) included; the benchmarks, the
tests and ``chip_smoke.py`` all call this one helper.
"""
from __future__ import annotations

from typing import Callable, Set

import jax
from jax.extend import core as jex_core

HOST_PRIMITIVES = frozenset({"pure_callback", "io_callback", "debug_callback",
                             "infeed", "outfeed", "device_put"})


def primitives(fn: Callable, *args) -> Set[str]:
    """Names of every primitive in ``fn(*args)``'s jaxpr, walked into
    every nested jaxpr."""
    prims: Set[str] = set()

    def walk(jaxpr):
        for eq in jaxpr.eqns:
            prims.add(eq.primitive.name)
            for v in eq.params.values():
                for s in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(s, jex_core.ClosedJaxpr):
                        walk(s.jaxpr)
                    elif isinstance(s, jex_core.Jaxpr):
                        walk(s)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return prims


def assert_no_host_callbacks(fn: Callable, *args) -> Set[str]:
    """Raise if ``fn(*args)`` holds a host-touching primitive; returns the
    primitive names so callers can check for more (e.g. ``"scan"``)."""
    prims = primitives(fn, *args)
    bad = prims & HOST_PRIMITIVES
    if bad:
        raise RuntimeError(f"compiled path touches the host: {sorted(bad)}")
    return prims
