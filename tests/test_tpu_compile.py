"""Compile rehearsal for a TPU v5e chip, run on the CPU.

The TPU compiler compiles for a described chip that is not attached; it
refuses what interpret mode accepts (block shapes off the tiling, 8-bit
scalars, too much VMEM).  These tests compile the Pallas kernels and the
stream programs of ``chip_smoke.py`` at its shapes, so a program the chip
would refuse fails here at no chip time.  Nothing runs: compiling is the
test.  The topology is described inside the fixture only — never while a
module is imported — and every test that needs it lives in this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps import echo
from repro.kernels.checksum.kernel import checksum_pallas
from repro.kernels.rs_encode import gf
from repro.kernels.rs_encode.kernel import rs_encode_pallas
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack, rpc_serve_topology

IP_S = F.ip("10.0.0.1")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host.  The persistent compile
    cache is off meanwhile: entries compiled for a described chip cannot
    be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _stream_hlo(stack, n_batches, batch, width, sharding):
    state = jax.tree.map(lambda x: _spec(x.shape, x.dtype, sharding),
                         jax.eval_shape(stack.init_state))
    return stack.stream_fn().lower(
        state, _spec((n_batches, batch, width), jnp.uint8, sharding),
        _spec((n_batches, batch), jnp.int32, sharding)).compile().as_text()


def test_rs_encode_kernel_compiles_at_serving_width(one_chip):
    # rs_serve encodes a batch of 64 4 KiB requests as (8, 64 * 512)
    bp = gf.bitplane_matrix(gf.generator_matrix(8, 2))
    hlo = jax.jit(lambda d: rs_encode_pallas(d, bp)).lower(
        _spec((8, 64 * 512), jnp.uint8, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_rs_encode_kernel_carries_its_name(one_chip):
    """The kernel's ``pallas_call`` is named, so the chip's trace names
    the custom call ``rs_encode`` whatever branch or scope holds it."""
    bp = gf.bitplane_matrix(gf.generator_matrix(8, 2))
    hlo = jax.jit(lambda d: rs_encode_pallas(d, bp)).lower(
        _spec((8, 4096), jnp.uint8, one_chip)).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert calls and all(ln.lstrip().startswith(("%rs_encode",
                                                 "ROOT %rs_encode"))
                         for ln in calls)


def test_checksum_kernel_compiles_at_arena_width(one_chip):
    hlo = jax.jit(checksum_pallas).lower(
        _spec((256, 1536), jnp.uint8, one_chip),
        _spec((256,), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_echo_stream_compiles_at_smoke_shapes(one_chip):
    """The packet path is XLA only: no kernel is expected in it."""
    stack = UdpStack([echo.make(port=7)], IP_S, mgmt_port=9909)
    hlo = _stream_hlo(stack, 64, 256, 1536, one_chip)
    assert "while" in hlo
    assert "tpu_custom_call" not in hlo


def test_rs_serve_stream_holds_the_compiled_kernel(one_chip):
    """Lowered for the chip, ``use_pallas`` selects the compiled kernel
    (never the interpreter) inside the stream program."""
    stack = UdpStack([], IP_S, topo=rpc_serve_topology(
        [("rs", "rs_serve", rpc.MSG_RS_ENCODE)],
        params={"rs": {"use_pallas": True}}))
    assert "tpu_custom_call" in _stream_hlo(stack, 4, 64, 4224, one_chip)
