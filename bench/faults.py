"""Faults planted under the timed path.  Each wraps the compiled step
(``fault(step) -> step``) and breaks one thing the configuration
guarantees; a run with any of them must come out not correct.

``udp_csum_zero`` is the control: replies leave with a zero UDP
checksum, which RFC 768 allows over IPv4 and which would spare the
program a pass over every reply, but which the configurations state
they do not do.  The others are the faults any served cell can have.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import wire


def _post(step, fn):
    post = jax.jit(fn)

    def wrapped(state, payload, length):
        state, outs = step(state, payload, length)
        return state, post(outs)
    return wrapped


def udp_csum_zero(step):
    """The control: every reply's UDP checksum field set to 0."""
    def fn(outs):
        outs = dict(outs)
        outs["tx_payload"] = outs["tx_payload"].at[
            ..., wire.UDP_CSUM:wire.UDP_CSUM + 2].set(0)
        return outs
    return _post(step, fn)


def answer_altered(step):
    """One byte of the first row of every batch flipped where the reply
    is produced: the first byte of its RPC body."""
    def fn(outs):
        outs = dict(outs)
        outs["tx_payload"] = outs["tx_payload"].at[..., 0, wire.HDR].add(1)
        return outs
    return _post(step, fn)


def half_batch(step):
    """The second half of every batch left out: its rows never leave."""
    def fn(outs):
        outs = dict(outs)
        half = outs["alive"].shape[-1] // 2
        outs["alive"] = outs["alive"].at[..., half:].set(False)
        return outs
    return _post(step, fn)


def state_unchanged(step):
    """A step that hands back the state it was given."""
    kept = {}

    def wrapped(state, payload, length):
        if "state" not in kept:
            kept["state"] = jax.tree.map(jnp.copy, state)
        _, outs = step(state, payload, length)
        return jax.tree.map(jnp.copy, kept["state"]), outs
    return wrapped


FAULTS = {f.__name__: f for f in (udp_csum_zero, answer_altered,
                                  half_batch, state_unchanged)}
