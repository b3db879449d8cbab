"""Public op: jit'd RS encode with kernel/oracle selection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rs_encode import gf
from repro.kernels.rs_encode.kernel import BLK, rs_encode_pallas
from repro.kernels.rs_encode.ref import rs_encode_jnp


@functools.lru_cache(maxsize=None)
def _mats(k: int, p: int):
    gm = gf.generator_matrix(k, p)
    return gm, gf.bitplane_matrix(gm)


@functools.partial(jax.jit, static_argnames=("k", "p", "use_pallas", "block"))
def rs_encode(data, k: int = 8, p: int = 2, use_pallas: bool = True,
              block: int = BLK):
    """data: (k, N) uint8 -> parity (p, N) uint8 for RS(k+p, k)."""
    gm, bp = _mats(k, p)
    if use_pallas:
        return rs_encode_pallas(data, bp, block=block)
    return rs_encode_jnp(data, gm)


def encode_blocks(blocks, k: int = 8, p: int = 2, use_pallas: bool = True):
    """blocks: (B, k*S) uint8 request payloads -> (B, p*S) parity, i.e. the
    paper's 4 KiB-in / 1 KiB-out RS(8,2) app semantics."""
    B, total = blocks.shape
    S = total // k
    data = blocks.reshape(B, k, S).transpose(1, 0, 2).reshape(k, B * S)
    pad = (-data.shape[1]) % BLK
    if pad and use_pallas:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    parity = rs_encode(data, k=k, p=p, use_pallas=use_pallas)
    parity = parity[:, :B * S].reshape(p, B, S).transpose(1, 0, 2)
    return parity.reshape(B, p * S)
