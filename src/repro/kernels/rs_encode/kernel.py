"""Pallas TPU kernel: Reed-Solomon (k, p) parity generation over GF(256).

Bit-plane formulation: for parity row j,
    parity_j = XOR_i XOR_b ( ((data_i >> b) & 1) * bp[j, i, b] )
— pure AND/shift/multiply/XOR vector ops on the VPU; no table gathers
(TPU has no efficient byte-gather; the FPGA's LUT multipliers become
bit-plane linear maps — see DESIGN.md hardware-adaptation notes).

The kernel works on 32-bit words holding four data bytes: the bit mask
``0x01010101`` picks bit b of all four bytes at once, and multiplying a
0/1-per-byte word by a constant below 256 never carries across bytes, so
the byte-wise formula holds lane for lane.  The coefficients ``bp`` are
static (they depend only on k and p) and are baked into the kernel as
immediates, so the chip's compiler sees only 32-bit vector work.

Block layout: data (k, N) uint8 is packed to (k, N/4) uint32 and tiled
along N into (k, BLK/4) VMEM blocks (k=8, BLK=4096 -> 32 KiB in + 8 KiB
out per step, MXU-free VPU work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import select_interpret

BLK = 4096
_LANES = np.uint32(0x01010101)


def _rs_kernel(data_ref, out_ref, *, bp):
    for j, row_coeffs in enumerate(bp):
        acc = jnp.zeros((1, data_ref.shape[1]), jnp.uint32)
        for i, coeffs in enumerate(row_coeffs):
            x = data_ref[i:i + 1, :]                  # (1, BLK/4) uint32
            for b, c in enumerate(coeffs):
                if c:
                    acc = acc ^ (((x >> b) & _LANES) * np.uint32(c))
        out_ref[j:j + 1, :] = acc


def _pack(data):
    """(k, N) uint8 -> (k, N/4) uint32, four bytes per word."""
    k, N = data.shape
    return jax.lax.bitcast_convert_type(data.reshape(k, N // 4, 4),
                                        jnp.uint32)


def _unpack(words):
    p, W = words.shape
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(p, W * 4)


def rs_encode_pallas(data, bitplanes, *, block: int = BLK):
    """data: (k, N) uint8; bitplanes: (p, k, 8) uint8 host array (the
    static coefficient table of :func:`gf.bitplane_matrix`) -> (p, N)
    uint8."""
    k, N = data.shape
    bp = tuple(tuple(tuple(int(c) for c in cs) for cs in row)
               for row in np.asarray(bitplanes))
    p = len(bp)
    if N % block or block % 4:
        raise ValueError(f"N={N} must be a multiple of block={block}, "
                         f"itself a multiple of 4")
    wblk = block // 4

    def call(words, interpret):
        return pl.pallas_call(
            functools.partial(_rs_kernel, bp=bp),
            grid=(N // block,),
            in_specs=[pl.BlockSpec((k, wblk), lambda n: (0, n))],
            out_specs=pl.BlockSpec((p, wblk), lambda n: (0, n)),
            out_shape=jax.ShapeDtypeStruct((p, N // 4), jnp.uint32),
            interpret=interpret,
            name="rs_encode",
        )(words)

    return _unpack(select_interpret(call, _pack(data)))
