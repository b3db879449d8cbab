"""Host-simulated multi-device meshes for tests and benchmarks.

XLA only honours ``--xla_force_host_platform_device_count`` if it is set
*before* the first jax import, so multi-device runs on a CPU box must
happen in a subprocess with a prepared environment.  This module is the
one place that pattern lives:

  * :func:`forced_env` — a subprocess environment forcing N host devices,
    pinned to the CPU platform;
  * :func:`run_script` — run a python snippet under that environment,
    with a guard prologue that prints :data:`UNAVAILABLE` and exits 0
    when the forcing did not take, so tests can skip instead of fail.

The child never opens an accelerator: a chip belongs to one process, and
a parent that has touched JAX already holds it.  Work that needs real
devices runs in the parent's own process.

No jax import here: importing this module never touches device state, so
a parent process can use it before (or without) initialising jax.
"""
from __future__ import annotations

import os
import subprocess
import sys
from typing import Optional

FLAG = "--xla_force_host_platform_device_count"
UNAVAILABLE = "HOSTMESH_UNAVAILABLE"

# prologue prepended to every run_script snippet: verify the forced
# device count actually materialised before the caller's code runs
_GUARD = """\
import jax, sys
if jax.device_count() < {n}:
    print("{marker}", jax.device_count())
    sys.exit(0)
"""


def forced_env(devices: int, base_env: Optional[dict] = None) -> dict:
    """A copy of ``base_env`` (default: os.environ) with ``XLA_FLAGS``
    forcing ``devices`` host platform devices (any prior forcing flag is
    replaced), ``JAX_PLATFORMS=cpu`` and ``PYTHONPATH`` including
    ``src``."""
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [p for p in env.get("XLA_FLAGS", "").split()
             if not p.startswith(FLAG)]
    flags.append(f"{FLAG}={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env.setdefault("PYTHONPATH", "src")
    return env


def run_script(script: str, devices: int = 8, timeout: int = 900,
               cwd: Optional[str] = None) -> subprocess.CompletedProcess:
    """Run ``script`` in a subprocess on a forced ``devices``-wide host
    mesh.  The guard prologue exits 0 printing :data:`UNAVAILABLE` when
    the platform refused the forcing — check ``UNAVAILABLE in
    result.stdout`` to skip rather than fail."""
    guarded = _GUARD.format(n=devices, marker=UNAVAILABLE) + script
    return subprocess.run([sys.executable, "-c", guarded],
                          env=forced_env(devices), capture_output=True,
                          text=True, timeout=timeout, cwd=cwd)
