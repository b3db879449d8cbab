"""Pallas TPU kernel: flash attention (prefill), GQA + sliding window.

Grid: (BH, nQ, nK) with the KV dimension innermost (sequential on TPU);
online-softmax running max / denominator / accumulator live in VMEM
scratch across KV iterations and are flushed to the output on the last KV
block.  Causal + window masking prunes by block before it prunes by
element.  Block sizes are 128-aligned for the MXU.

q is laid out (B*H, S, hd); k/v are (B*KV, S, hd) — the index map folds
the GQA group so each q head reads its kv head's blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import select_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, d_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        d_scr[...] = jnp.zeros_like(d_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)               # (bq, hd)
    k = k_ref[0].astype(jnp.float32)               # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    d_scr[...] = d_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _flush():
        d = jnp.maximum(d_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / d[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 256, bk: int = 256):
    """q: (BH, S, hd); k, v: (BKV, S, hd) with BH = BKV * G.
    Returns (BH, S, hd)."""
    BH, S, hd = q.shape
    BKV = k.shape[0]
    G = BH // BKV
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    n_q, n_k = S // bq, S // bk
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, n_k=n_k)

    def call(q, k, v, interpret):
        return pl.pallas_call(
            kernel,
            grid=(BH, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, hd),
                             lambda b, i, j, G=G: (b // G, j, 0)),
                pl.BlockSpec((1, bk, hd),
                             lambda b, i, j, G=G: (b // G, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq,), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v)

    return select_interpret(call, q, k, v)
