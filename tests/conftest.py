"""Shared fixtures.

`forced_host_mesh` is the one way tests get a multi-device mesh on a CPU
box: XLA only honours ``--xla_force_host_platform_device_count`` before
the first jax import, so the snippet runs in a subprocess with a
prepared environment (repro.launch.hostmesh), pinned to the CPU.  When
the forcing does not take, the run is *skipped* with a clear message
instead of failing.
"""
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def forced_host_mesh():
    """Callable fixture: ``forced_host_mesh(script, devices=8)`` runs the
    python snippet on a host-simulated mesh and returns its stdout.
    Asserts a zero exit (stderr tail in the failure message); skips when
    the device forcing did not take."""
    from repro.launch import hostmesh

    def run(script: str, devices: int = 8, timeout: int = 900) -> str:
        out = hostmesh.run_script(script, devices=devices,
                                  timeout=timeout, cwd=_REPO)
        if hostmesh.UNAVAILABLE in out.stdout:
            pytest.skip(f"platform will not simulate {devices} host "
                        f"devices (got: {out.stdout.strip()})")
        assert out.returncode == 0, out.stderr[-3000:]
        return out.stdout

    return run
