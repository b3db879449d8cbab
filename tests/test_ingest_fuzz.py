"""Ingest-path hardening: no malformed input may raise anywhere on the
RPC/UDP serving path.

Regression coverage for the PR's bugfix satellites:
  * runt UDP headers (udp_len < 8) are rejected AND the returned payload
    length is clamped non-negative (it used to go negative and poison
    every downstream length computation);
  * `decode_request` / `decode_reply` are bounds-checked (ok-flag
    convention mirroring rpc.parse) — truncated payloads used to raise
    ``struct.error``;
  * `LmServerApp` frees sessions: LRU eviction on slot exhaustion (or an
    ERR_NO_SLOT reply with eviction disabled — never a RuntimeError),
    plus explicit MSG_LM_RELEASE close;
  * fuzz properties (hypothesis when available, the deterministic
    `_hyp_compat` fallback otherwise): random and truncated bytes
    through the udp + rpc parse chain and the app codecs never raise,
    and truncated frames always parse as ok=False.
"""
import struct

import jax.numpy as jnp
import numpy as np
import pytest

from tests._hyp_compat import given, settings, st

from repro.apps import lm_server
from repro.apps.lm_server import (ERR_BAD_REQUEST, ERR_NO_SESSION,
                                  ERR_NO_SLOT, LmServerApp, decode_reply,
                                  decode_request, encode_reply,
                                  encode_request, reply_error)
from repro.net import eth, frames as F, ipv4, rpc, udp

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")


def udp_meta(n):
    return {"src_ip": jnp.full((n,), IP_C, jnp.uint32),
            "dst_ip": jnp.full((n,), IP_S, jnp.uint32)}


def parse_chain(frames, max_len=160):
    """Full rx parse (eth -> ip -> udp -> rpc) over raw frame bytes;
    returns the conjunction of every ok flag plus the udp/rpc lengths."""
    p, l = F.to_batch(frames, max_len)
    p, l = jnp.asarray(p), jnp.asarray(l)
    p, l, m = eth.parse(p, l)
    p, l, m2, ok1 = ipv4.parse(p, l)
    m.update(m2)
    p, plen, m3, ok2 = udp.parse(p, l, m)
    body, blen, rmeta, ok3 = rpc.parse(p, plen)
    return np.asarray(ok1 & ok2 & ok3), np.asarray(plen), np.asarray(blen)


# ---------------------------------------------------------------------------
# runt UDP header (deterministic regression)


def test_udp_runt_header_rejected_and_clamped():
    """udp_len in [0, 8) is a runt header: ok must drop and the returned
    payload length must clamp to zero, never go negative."""
    body = b"abcd"
    dgrams = [struct.pack("!HHHH", 5000, 9400, ulen, 0) + body
              for ulen in range(0, 8)]             # checksum 0 = disabled
    dgrams.append(struct.pack("!HHHH", 5000, 9400, 8 + len(body), 0) + body)
    p, l = F.to_batch(dgrams, 32)
    n = len(dgrams)
    _, plen, _, ok = udp.parse(jnp.asarray(p), jnp.asarray(l), udp_meta(n))
    ok, plen = np.asarray(ok), np.asarray(plen)
    assert not ok[:8].any()                        # every runt rejected
    assert (plen >= 0).all()                       # clamped, not negative
    assert bool(ok[8]) and plen[8] == len(body)    # well-formed still parses


def test_udp_len_beyond_buffer_rejected():
    dg = struct.pack("!HHHH", 5000, 9400, 200, 0) + b"xy"
    p, l = F.to_batch([dg], 32)
    _, plen, _, ok = udp.parse(jnp.asarray(p), jnp.asarray(l), udp_meta(1))
    assert not bool(ok[0])


# ---------------------------------------------------------------------------
# fuzz: the frame parse chain never raises, truncation never parses ok


@settings(max_examples=30)
@given(st.binary(min_size=0, max_size=150))
def test_fuzz_random_bytes_never_raise(blob):
    ok, plen, blen = parse_chain([blob])
    assert (plen >= 0).all() and (blen >= 0).all()


@settings(max_examples=30)
@given(st.integers(min_value=0, max_value=60))
def test_fuzz_truncated_frame_parses_not_ok(cut):
    frame = F.udp_rpc_frame(IP_C, IP_S, 5000, 9400,
                            rpc.np_frame(rpc.MSG_LM_GENERATE, 1,
                                         encode_request(7, 2, [1, 2, 3])))
    cut = min(cut, len(frame) - 1)
    ok, plen, blen = parse_chain([frame, frame[:cut]])
    assert bool(ok[0])                             # intact frame parses
    assert not bool(ok[1])                         # any truncation: not ok
    assert (plen >= 0).all() and (blen >= 0).all()


# ---------------------------------------------------------------------------
# app codecs: bounds-checked, ok-flag convention (used to raise)


def test_truncated_request_and_reply_decode_not_ok():
    req = encode_request(7, 2, [5, 6, 7])
    for k in range(len(req)):
        _, _, _, ok = decode_request(req[:k])
        assert not ok
    assert decode_request(req) == (7, 2, [5, 6, 7], True)

    rep = encode_reply(7, [1, 2, 3])
    for k in range(len(rep)):
        _, _, ok = decode_reply(rep[:k])
        assert not ok
    assert decode_reply(rep) == (7, [1, 2, 3], True)


def test_codec_carries_tokens_beyond_16_bits():
    """Published vocabularies outgrow u16 (qwen1.5: 151,936 tokens)."""
    toks = [151935, 65536, 3]
    assert decode_request(encode_request(7, 1, toks)) == (7, 1, toks, True)
    assert decode_reply(encode_reply(7, toks)) == (7, toks, True)


def test_error_reply_roundtrip():
    rep = lm_server.encode_error(9, ERR_NO_SESSION)
    assert decode_reply(rep) == (9, [], True)
    assert reply_error(rep) == ERR_NO_SESSION
    assert reply_error(encode_reply(9, [4])) is None


@settings(max_examples=30)
@given(st.binary(min_size=0, max_size=40))
def test_fuzz_codecs_never_raise(blob):
    decode_request(blob)
    decode_reply(blob)
    reply_error(blob)


# ---------------------------------------------------------------------------
# session lifecycle: eviction / release / exhaustion (host path)


class FakeEngine:
    """ServeEngine's session-slot surface without the model: generate()
    tags tokens with the slot id so tests can see who answered."""

    def __init__(self, max_sessions=2):
        self.M = max_sessions
        self.used = np.zeros((max_sessions,), bool)

    def has_free_slot(self):
        return bool((~self.used).any())

    def new_session(self, prompt_tokens):
        free = np.where(~self.used)[0]
        if not len(free):
            raise RuntimeError("no free session slots")
        sid = int(free[0])
        self.used[sid] = True
        return sid

    def release(self, sid):
        self.used[sid] = False

    def generate(self, sid, n):
        return [100 + sid] * n


def test_lru_eviction_on_slot_exhaustion():
    app = LmServerApp(FakeEngine(2))
    for s in (1, 2):
        assert reply_error(app.handle(encode_request(s, 1, [s]))) is None
    # session 1 is LRU -> a third client evicts it, not an error
    assert reply_error(app.handle(encode_request(3, 1, [3]))) is None
    assert set(app.session_map) == {2, 3}
    # touching 2 re-orders the LRU list: next eviction takes 3
    app.handle(encode_request(2, 1, []))
    app.handle(encode_request(4, 1, [4]))
    assert set(app.session_map) == {2, 4}
    # the evicted session's follow-up (no prompt) is an error reply
    assert reply_error(app.handle(encode_request(1, 1, []))) == \
        ERR_NO_SESSION


def test_no_evict_mode_returns_error_reply():
    app = LmServerApp(FakeEngine(1), evict=None)
    assert reply_error(app.handle(encode_request(1, 1, [1]))) is None
    reply = app.handle(encode_request(2, 1, [2]))   # full: reply, no raise
    assert reply_error(reply) == ERR_NO_SLOT
    assert set(app.session_map) == {1}


def test_release_frees_the_slot():
    app = LmServerApp(FakeEngine(1), evict=None)
    app.handle(encode_request(1, 1, [1]))
    rel = app.handle_release(lm_server.encode_release(1))
    assert decode_reply(rel) == (1, [], True)
    assert app.session_map == {} and app.engine.has_free_slot()
    assert reply_error(app.handle(encode_request(2, 1, [2]))) is None
    # releasing an unknown / already-closed session is an error reply
    assert reply_error(app.handle_release(lm_server.encode_release(1))) == \
        ERR_NO_SESSION
    assert reply_error(app.handle_release(b"\x01")) == ERR_BAD_REQUEST


def test_malformed_request_gets_error_reply():
    app = LmServerApp(FakeEngine(1))
    assert reply_error(app.handle(b"")) == ERR_BAD_REQUEST
    assert reply_error(app.handle(b"\x00\x00\x00\x07\x00")) == \
        ERR_BAD_REQUEST
    # header claims more prompt tokens than the payload carries
    trunc = encode_request(7, 1, [1, 2, 3])[:-2]
    assert reply_error(app.handle(trunc)) == ERR_BAD_REQUEST
    assert app.session_map == {}                   # nothing half-opened
