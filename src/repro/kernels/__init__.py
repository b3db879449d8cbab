"""Pallas kernels for the stack's compute hot spots (``<name>/kernel.py``
with an ``ops.py`` entry point and a ``ref.py`` oracle each).

Every kernel is launched through :func:`select_interpret`: the Pallas
interpreter is for the CPU only, and on the chip the kernel is always
compiled.
"""
from __future__ import annotations

import jax


def select_interpret(call, *args):
    """``call(*args, interpret)`` with ``interpret`` chosen by the platform
    the program is lowered for: True on the CPU (the tests), False on every
    other platform.  The choice is made at lowering time
    (``lax.platform_dependent``), so a program lowered for a TPU — on the
    chip, or for a described one in a compile rehearsal — holds the
    compiled kernel even when the process's default backend is the CPU."""
    return jax.lax.platform_dependent(
        *args, cpu=lambda *a: call(*a, True),
        default=lambda *a: call(*a, False))
