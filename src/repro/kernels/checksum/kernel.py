"""Pallas TPU kernel: RFC 1071 internet checksum over packet batches.

The per-byte hot spot of the protocol tiles (eth/ip/udp parse each touch
every payload byte).  The (B, L) uint8 frames are paired into (B, L/2)
big-endian 16-bit words (int32) outside the kernel; the kernel masks each
word's bytes to the packet length, sums and folds, in blocks of BB
packets.  Every block is 2-D and 32-bit, as the chip's tiling wants.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import select_interpret

BB = 8          # packets per block


def _csum_kernel(words_ref, len_ref, out_ref):
    words = words_ref[...]                          # (BB, L/2) int32
    length = len_ref[...]                           # (BB, 1) int32
    byte0 = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1) * 2
    hi = jnp.where(byte0 < length, words & 0xFF00, 0)
    lo = jnp.where(byte0 + 1 < length, words & 0xFF, 0)
    total = jnp.sum(hi | lo, axis=1, keepdims=True)     # < 2^31 for L < 64 KiB
    total = (total & 0xFFFF) + (total >> 16)
    total = (total & 0xFFFF) + (total >> 16)
    out_ref[...] = (~total) & 0xFFFF


def checksum_pallas(payload, length):
    """payload: (B, L) uint8 (L even), length: (B,) int32 -> (B,) uint32."""
    B, L = payload.shape
    if L % 2:
        raise ValueError(f"frame width {L} must be even")
    pad = (-B) % BB
    if pad:
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
        length = jnp.pad(length, ((0, pad),))
    Bp = payload.shape[0]
    p = payload.astype(jnp.int32)
    words = (p[:, 0::2] << 8) | p[:, 1::2]
    lens = length.astype(jnp.int32).reshape(Bp, 1)

    def call(words, lens, interpret):
        return pl.pallas_call(
            _csum_kernel,
            grid=(Bp // BB,),
            in_specs=[pl.BlockSpec((BB, L // 2), lambda b: (b, 0)),
                      pl.BlockSpec((BB, 1), lambda b: (b, 0))],
            out_specs=pl.BlockSpec((BB, 1), lambda b: (b, 0)),
            out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            interpret=interpret,
        )(words, lens)

    out = select_interpret(call, words, lens)
    return out[:B, 0].astype(jnp.uint32)
