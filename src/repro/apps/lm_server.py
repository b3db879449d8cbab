"""LM serving application behind the Beehive stack.

Requests arrive as RPC-over-UDP (MSG_LM_GENERATE):
  payload = [session u32 | n_gen u16 | n_prompt u16 | prompt tokens u32...]
Reply:
  payload = [session u32 | n_out u16 | tokens u32 ...]
Tokens are 32-bit: published vocabularies outgrow 16 bits (qwen1.5's has
151,936 entries).
An error reply carries a sentinel in n_out (>= ERR_BASE) and no tokens.
MSG_LM_RELEASE closes a session explicitly: payload = [session u32].

Two serving paths share this wire format:

  * **host-mediated** (`LmServerApp.handle`): the CPU-attached baseline —
    the host parses the request, drives the `ServeEngine`, and frames the
    reply.  Sessions are LRU-tracked; slot exhaustion evicts (or returns an
    error reply) instead of raising.
  * **direct-attached** (`make_tile` + the `lm_serve` tile in net/tiles.py):
    the paper's headline path — session/KV state lives in the compiled
    stack's state pytree (the `run_stream` scan carry), and each arriving
    MSG_LM_GENERATE triggers one on-device decode step with the reply built
    in the same device program.  Prompts are prefilled host-side via the
    engine and *adopted* into device state (`adopt_engine`); thereafter the
    ingest -> decode -> reply loop never touches the host.

Sessions are flows: the upstream dispatch pins a session to an engine
replica; live migration moves the session blob between engines and flips
the dispatch table (paper §5.3 semantics, with the KV cache playing the
role of the TCP connection state).
"""
from __future__ import annotations

import dataclasses
import struct
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.net import bytesops as B
from repro.serve.engine import ServeEngine

REQ_HLEN = 8           # session u32 | n_gen u16 | n_prompt u16
REP_HLEN = 6           # session u32 | n_out u16

# error sentinels carried in the reply's n_out field (a real reply can
# never reach them: tokens are u32, so n_out tops out near payload_len/4)
ERR_BASE = 0xFFF0
ERR_BAD_REQUEST = 0xFFFF   # malformed / truncated request payload
ERR_NO_SLOT = 0xFFFE       # engine full and eviction disabled
ERR_NO_SESSION = 0xFFFD    # unknown session (or no prompt to open one)


def encode_request(session: int, n_gen: int, prompt: List[int]) -> bytes:
    return struct.pack("!IHH", session, n_gen, len(prompt)) + \
        b"".join(struct.pack("!I", t) for t in prompt)


def encode_release(session: int) -> bytes:
    return struct.pack("!I", session)


def decode_request(payload: bytes) -> Tuple[int, int, List[int], bool]:
    """Bounds-checked parse mirroring rpc.parse's ok-flag convention:
    returns (session, n_gen, prompt, ok) and never raises on truncation."""
    if len(payload) < REQ_HLEN:
        return 0, 0, [], False
    session, n_gen, n_prompt = struct.unpack("!IHH", payload[:REQ_HLEN])
    end = REQ_HLEN + 4 * n_prompt
    if end > len(payload):
        return session, n_gen, [], False
    toks = list(struct.unpack(f"!{n_prompt}I", payload[REQ_HLEN:end])) \
        if n_prompt else []
    return session, n_gen, toks, True


def encode_reply(session: int, tokens: List[int]) -> bytes:
    return struct.pack("!IH", session, len(tokens)) + \
        b"".join(struct.pack("!I", t) for t in tokens)


def encode_error(session: int, code: int) -> bytes:
    assert code >= ERR_BASE
    return struct.pack("!IH", session, code)


def decode_reply(payload: bytes) -> Tuple[int, List[int], bool]:
    """Returns (session, tokens, ok).  Error replies decode as
    (session, [], True) — use :func:`reply_error` to read the code."""
    if len(payload) < REP_HLEN:
        return 0, [], False
    session, n = struct.unpack("!IH", payload[:REP_HLEN])
    if n >= ERR_BASE:
        return session, [], True
    end = REP_HLEN + 4 * n
    if end > len(payload):
        return session, [], False
    toks = list(struct.unpack(f"!{n}I", payload[REP_HLEN:end])) if n else []
    return session, toks, True


def reply_error(payload: bytes) -> Optional[int]:
    """The error sentinel of a reply, or None for a success reply."""
    if len(payload) < REP_HLEN:
        return None
    _, n = struct.unpack("!IH", payload[:REP_HLEN])
    return n if n >= ERR_BASE else None


class LmServerApp:
    """Host-side application loop around a ServeEngine (the CPU-attached
    baseline).  Sessions are LRU-ordered; when the engine is full a new
    session evicts the least-recently-used one (``evict="lru"``, default)
    or gets an ERR_NO_SLOT reply (``evict=None``).  Malformed requests get
    an error reply — no ingest path raises."""

    def __init__(self, engine: ServeEngine, evict: Optional[str] = "lru"):
        self.engine = engine
        self.evict = evict
        self.session_map: "OrderedDict[int, int]" = OrderedDict()

    def handle(self, payload: bytes) -> bytes:
        session, n_gen, prompt, ok = decode_request(payload)
        if not ok:
            return encode_error(session, ERR_BAD_REQUEST)
        if session not in self.session_map:
            if not prompt:
                # a follow-up for a session we don't hold (evicted, or
                # never opened) — nothing to prefill from
                return encode_error(session, ERR_NO_SESSION)
            if not self.engine.has_free_slot():
                if self.evict == "lru" and self.session_map:
                    victim = next(iter(self.session_map))
                    self.release(victim)
                else:
                    return encode_error(session, ERR_NO_SLOT)
            try:
                sid = self.engine.new_session(np.asarray(prompt, np.int32))
            except RuntimeError:
                return encode_error(session, ERR_NO_SLOT)
            self.session_map[session] = sid
        self.session_map.move_to_end(session)
        sid = self.session_map[session]
        toks = self.engine.generate(sid, n_gen)
        return encode_reply(session, toks)

    def handle_release(self, payload: bytes) -> bytes:
        """MSG_LM_RELEASE: explicit session close."""
        if len(payload) < 4:
            return encode_error(0, ERR_BAD_REQUEST)
        session = struct.unpack("!I", payload[:4])[0]
        if self.release(session):
            return encode_reply(session, [])
        return encode_error(session, ERR_NO_SESSION)

    def release(self, session: int) -> bool:
        sid = self.session_map.pop(session, None)
        if sid is None:
            return False
        self.engine.release(sid)
        return True

    # ---- migration --------------------------------------------------------
    def migrate_session_to(self, session: int, other: "LmServerApp") -> None:
        sid = self.session_map.pop(session)
        blob = self.engine.migrate_out(sid)
        other.session_map[session] = other.engine.migrate_in(blob)


# ---------------------------------------------------------------------------
# direct-attached serving: the device-resident LM tile
#
# The tile's state (params / cache / pos / last_tok / used / sess_ids)
# lives in the compiled stack's state pytree, so `run_stream` threads it
# through the lax.scan carry — a request arriving in batch i advances its
# session for batch i+1 with zero host involvement.  Prompts are
# prefilled host-side through the ordinary ServeEngine and adopted via
# `adopt_engine`.


@dataclasses.dataclass
class LmTileDecl:
    """Binding for a `lm_serve` tile (passed to the compiler by node name,
    like an AppDecl).  `state` is the template the tile init copies; it
    holds the weights under ``"params"``, so they reach the compiled
    stream program as arguments and are never folded into it as
    constants."""
    name: str
    cfg: Any
    max_sessions: int
    max_seq: int
    state: Dict[str, Any]


def make_tile(cfg, params, max_sessions: int = 4, max_seq: int = 64,
              name: str = "lm") -> LmTileDecl:
    from repro.models import model
    state = {
        "params": params,
        "cache": model.init_cache(cfg, max_sessions, max_seq),
        "pos": jnp.zeros((max_sessions,), jnp.int32),
        "last_tok": jnp.zeros((max_sessions,), jnp.int32),
        "used": jnp.zeros((max_sessions,), bool),
        "sess_ids": jnp.zeros((max_sessions,), jnp.uint32),
        "served": jnp.zeros((), jnp.int32),
    }
    return LmTileDecl(name=name, cfg=cfg, max_sessions=max_sessions,
                      max_seq=max_seq, state=state)


def adopt_engine(tile_state: Dict[str, Any], engine: ServeEngine,
                 session_map: Dict[int, int]) -> Dict[str, Any]:
    """Install a host-prefilled engine's sessions into a device tile state
    (e.g. ``state["apps"]["lm"]``).  `session_map` maps client session id
    -> engine slot (`LmServerApp.session_map` works as-is).  Arrays are
    copied, so a donated stream run can never invalidate the engine's own
    buffers."""
    M = engine.M
    ids = np.zeros((M,), np.uint32)
    used = np.zeros((M,), bool)
    for sess, slot in session_map.items():
        ids[slot] = sess
        used[slot] = bool(engine.used[slot])
    st = dict(tile_state)
    st.update(
        cache=jax.tree.map(jnp.array, engine.cache),
        pos=jnp.array(engine.pos),
        last_tok=jnp.array(engine.last_tok),
        used=jnp.asarray(used),
        sess_ids=jnp.asarray(ids),
    )
    return st


def tile_process(decl: LmTileDecl, st: Dict[str, Any], body, blen, active):
    """One batch through the device LM tile: parse requests, run ONE decode
    step for every session addressed by a valid request, build replies.

    Pure JAX — no host callbacks, jittable inside the run_stream scan.
    Semantics: a request generates exactly one token (clients stream
    follow-up requests for more, the serving decode loop); duplicate
    requests for one session within a batch coalesce into a single step.
    Invalid rows (short body, unknown session, out-of-room session) get an
    error reply and advance nothing.
    """
    from repro.models import model
    cfg, params, S = decl.cfg, st["params"], decl.max_seq

    sess = B.be32(body, 0)                              # (B,) uint32
    n_gen = B.be16(body, 4)
    ok_len = (blen >= REQ_HLEN) & (n_gen >= 1)
    match = st["used"][None, :] & (st["sess_ids"][None, :] == sess[:, None])
    hit = match.any(axis=1)
    slot = jnp.argmax(match, axis=1)                    # (B,) garbage if ~hit
    room = (st["pos"] < S)[slot]
    valid = active & ok_len & hit & room
    adv = (match & valid[:, None]).any(axis=0)          # (M,) sessions to step

    def run_step(cache, last_tok, pos):
        logits, ncache = model.decode_step(cfg, params, cache, last_tok, pos)
        return ncache, model.greedy_token(cfg, logits)

    def skip_step(cache, last_tok, pos):
        return cache, last_tok

    # skip the model entirely on batches with no LM traffic (mixed streams)
    cache, nxt = jax.lax.cond(adv.any(), run_step, skip_step,
                              st["cache"], st["last_tok"], st["pos"])
    new_pos = st["pos"] + adv.astype(jnp.int32)
    new_last = jnp.where(adv, nxt, st["last_tok"])

    tok = new_last[slot].astype(jnp.uint32)             # (B,)
    out = jnp.zeros_like(body)
    out = B.set_be32(out, 0, sess)
    n_out = jnp.where(
        valid, jnp.uint32(1),
        jnp.where(~ok_len, jnp.uint32(ERR_BAD_REQUEST),
                  jnp.where(~hit, jnp.uint32(ERR_NO_SESSION),
                            jnp.uint32(ERR_NO_SLOT))))   # session out of room
    out = B.set_be16(out, 4, n_out)
    out = B.set_be32(out, 6, jnp.where(valid, tok, jnp.uint32(0)))
    out_blen = jnp.where(valid, REP_HLEN + 4, REP_HLEN).astype(blen.dtype)

    new_st = dict(st)
    new_st.update(cache=cache, pos=new_pos, last_tok=new_last,
                  served=st["served"] + valid.sum(dtype=jnp.int32))
    return new_st, out, out_blen
