"""Vectorized byte-level packet operations.

Payloads are (B, L) uint8 tensors with per-packet valid lengths.  All
helpers are jittable and operate on whole batches — the TPU analog of the
FPGA's per-flit header parse/realign datapath.

The header shifts and the checksums run under the named scopes
``bytes/shift`` and ``bytes/csum``: every HLO op they emit carries the
scope in its ``op_name`` metadata, so a device trace can say how much of
each stage's time they take.  Scopes change no computation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# field reads (big-endian network order)


def be16(payload, off):
    """(B, L) uint8, static or (B,) offset -> (B,) uint32."""
    if isinstance(off, int):
        hi = payload[:, off].astype(jnp.uint32)
        lo = payload[:, off + 1].astype(jnp.uint32)
    else:
        hi = jnp.take_along_axis(payload, off[:, None], 1)[:, 0].astype(jnp.uint32)
        lo = jnp.take_along_axis(payload, off[:, None] + 1, 1)[:, 0].astype(jnp.uint32)
    return (hi << 8) | lo


def be32(payload, off):
    if isinstance(off, int):
        b = [payload[:, off + i].astype(jnp.uint32) for i in range(4)]
    else:
        b = [jnp.take_along_axis(payload, off[:, None] + i, 1)[:, 0]
             .astype(jnp.uint32) for i in range(4)]
    return (b[0] << 24) | (b[1] << 16) | (b[2] << 8) | b[3]


def u8(payload, off):
    if isinstance(off, int):
        return payload[:, off].astype(jnp.uint32)
    return jnp.take_along_axis(payload, off[:, None], 1)[:, 0].astype(jnp.uint32)


# ---------------------------------------------------------------------------
# field writes


def set_u8(payload, off: int, val):
    return payload.at[:, off].set(val.astype(jnp.uint8))


def set_be16(payload, off: int, val):
    v = val.astype(jnp.uint32)
    payload = payload.at[:, off].set((v >> 8).astype(jnp.uint8))
    return payload.at[:, off + 1].set((v & 0xFF).astype(jnp.uint8))


def set_be32(payload, off: int, val):
    v = val.astype(jnp.uint32)
    for i, sh in enumerate((24, 16, 8, 0)):
        payload = payload.at[:, off + i].set(((v >> sh) & 0xFF).astype(jnp.uint8))
    return payload


# ---------------------------------------------------------------------------
# header strip / prepend (data realignment)


def _static_shift(payload, s: int, right: bool):
    """Every row moved s bytes toward the head (or the tail), zero-filled:
    a slice and a pad."""
    L = payload.shape[1]
    s = min(s, L)
    if right:
        return jnp.pad(payload[:, :L - s], ((0, 0), (s, 0)))
    return jnp.pad(payload[:, s:], ((0, 0), (0, s)))


def _shift(payload, n, mask, right):
    """A static n is one slice and pad.  A per-row n is a barrel shifter:
    for each bit b of n, a select between the rows as they are and the
    rows moved 2**b bytes, so no per-byte gather.  Every n is exact, and
    n >= L gives zeros."""
    L = payload.shape[1]
    if isinstance(n, int):
        out = _static_shift(payload, n, right)
    else:
        n = jnp.clip(n, 0, L)[:, None]
        out = payload
        for b in range(L.bit_length()):
            out = jnp.where((n >> b) & 1 == 1,
                            _static_shift(out, 1 << b, right), out)
    if mask is not None:
        out = jnp.where(mask[:, None], out, payload)
    return out


def shift_left(payload, n, mask=None):
    """Strip n leading bytes per packet (n: static int or (B,) int32); the
    last n bytes of a row become zero."""
    with jax.named_scope("bytes/shift"):
        return _shift(payload, n, mask, right=False)


def shift_right(payload, n, mask=None):
    """Make room for an n-byte header (contents shifted toward the tail,
    the first n bytes zero)."""
    with jax.named_scope("bytes/shift"):
        return _shift(payload, n, mask, right=True)


def write_bytes(payload, off: int, data):
    """Write (B, n) bytes at a static offset."""
    n = data.shape[1]
    return jax.lax.dynamic_update_slice(
        payload, data.astype(jnp.uint8), (0, off))


# ---------------------------------------------------------------------------
# RFC 1071 internet checksum


def checksum16(payload, start, length):
    """Ones-complement 16-bit checksum over [start, start+length) per packet.
    start: static int; length: (B,) int32.  Returns (B,) uint32 (already
    complemented, network order)."""
    with jax.named_scope("bytes/csum"):
        B, L = payload.shape
        idx = jnp.arange(L - start)
        seg = payload[:, start:].astype(jnp.uint32)
        valid = idx[None, :] < length[:, None]
        seg = jnp.where(valid, seg, 0)
        if seg.shape[1] % 2:
            seg = jnp.pad(seg, ((0, 0), (0, 1)))
        words = (seg[:, 0::2] << 8) | seg[:, 1::2]
        total = words.sum(axis=1, dtype=jnp.uint32)
        for _ in range(3):                       # fold carries
            total = (total & 0xFFFF) + (total >> 16)
        return (~total) & jnp.uint32(0xFFFF)


def pseudo_header_sum(src_ip, dst_ip, proto, tcp_len):
    """IPv4 pseudo-header contribution for UDP/TCP checksums (unfolded)."""
    s = (src_ip >> 16) + (src_ip & 0xFFFF)
    s = s + (dst_ip >> 16) + (dst_ip & 0xFFFF)
    s = s + proto.astype(jnp.uint32) + tcp_len.astype(jnp.uint32)
    return s


def checksum16_with_pseudo(payload, start, length, pseudo):
    """Checksum including a pseudo-header partial sum."""
    with jax.named_scope("bytes/csum"):
        B, L = payload.shape
        idx = jnp.arange(L - start)
        seg = payload[:, start:].astype(jnp.uint32)
        valid = idx[None, :] < length[:, None]
        seg = jnp.where(valid, seg, 0)
        if seg.shape[1] % 2:
            seg = jnp.pad(seg, ((0, 0), (0, 1)))
        words = (seg[:, 0::2] << 8) | seg[:, 1::2]
        total = words.sum(axis=1, dtype=jnp.uint32) + pseudo.astype(jnp.uint32)
        for _ in range(3):
            total = (total & 0xFFFF) + (total >> 16)
        return (~total) & jnp.uint32(0xFFFF)


# ---------------------------------------------------------------------------
# numpy-side golden frame builders (for tests/benchmarks; Linux wire format)


def np_checksum16(data: bytes) -> int:
    import numpy as np
    b = np.frombuffer(data, dtype=np.uint8).astype(np.uint32)
    if len(b) % 2:
        b = np.append(b, 0)
    total = int(((b[0::2] << 8) | b[1::2]).sum())
    while total >> 16:
        total = (total & 0xFFFF) + (total >> 16)
    return (~total) & 0xFFFF
