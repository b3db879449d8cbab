"""Pallas TPU kernel: fused Mamba-1 selective scan.

The XLA fallback (associative scan) materializes O(S * D * N * log chunk)
fp32 intermediates in HBM — the dominant memory-roofline term for
falcon-mamba (EXPERIMENTS.md §Roofline).  This kernel fuses the recurrence:
inputs u, dt (B, S, D), Bm, Cm (B, S, N), A (D, N); the state h (bd, N)
lives in VMEM scratch across sequence blocks, and only u/dt/Bm/Cm/y ever
touch HBM — O(S * D) traffic, an ~N*log(Q) ≈ 2 orders-of-magnitude cut.

Grid: (B, nD, nS) — sequence innermost (sequential), h persists across it.
Inside a block the timestep loop is a lax.fori over bs steps of (bd, N)
vector ops (VPU work, no MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import select_interpret


def _mamba_kernel(u_ref, dt_ref, bm_ref, cm_ref, a_ref, y_ref, h_scr, *,
                  bs: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    u = u_ref[0].astype(jnp.float32)       # (bs, bd)
    dt = dt_ref[0].astype(jnp.float32)     # (bs, bd)
    bm = bm_ref[0].astype(jnp.float32)     # (bs, N)
    cm = cm_ref[0].astype(jnp.float32)     # (bs, N)
    A = a_ref[...].astype(jnp.float32)     # (bd, N)

    def step(t, carry):
        h, ys = carry
        decay = jnp.exp(dt[t][:, None] * A)                 # (bd, N)
        h = decay * h + (dt[t] * u[t])[:, None] * bm[t][None, :]
        y_t = jnp.sum(h * cm[t][None, :], axis=1)           # (bd,)
        ys = jax.lax.dynamic_update_index_in_dim(ys, y_t, t, 0)
        return h, ys

    ys0 = jnp.zeros((bs,) + h_scr.shape[:1], jnp.float32)
    h, ys = jax.lax.fori_loop(0, bs, step, (h_scr[...], ys0))
    h_scr[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)


def mamba_scan_pallas(u, dt, bm, cm, A, *, bd: int = 512, bs: int = 256):
    """u, dt: (B, S, D); bm, cm: (B, S, N); A: (D, N) -> y (B, S, D)
    where y[b,t,d] = sum_n C[b,t,n] * h[b,t,d,n] (the D*u skip and gating
    stay in the caller)."""
    B, S, D = u.shape
    N = bm.shape[-1]
    bd = min(bd, D)
    bs = min(bs, S)
    assert D % bd == 0 and S % bs == 0
    grid = (B, D // bd, S // bs)
    kernel = functools.partial(_mamba_kernel, bs=bs)

    def call(u, dt, bm, cm, A, interpret):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bs, bd), lambda b, d, s: (b, s, d)),
                pl.BlockSpec((1, bs, bd), lambda b, d, s: (b, s, d)),
                pl.BlockSpec((1, bs, N), lambda b, d, s: (b, s, 0)),
                pl.BlockSpec((1, bs, N), lambda b, d, s: (b, s, 0)),
                pl.BlockSpec((bd, N), lambda b, d, s: (d, 0)),
            ],
            out_specs=pl.BlockSpec((1, bs, bd), lambda b, d, s: (b, s, d)),
            out_shape=jax.ShapeDtypeStruct((B, S, D), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
            interpret=interpret,
        )(u, dt, bm, cm, A)

    return select_interpret(call, u, dt, bm, cm, A)
