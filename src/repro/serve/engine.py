"""LM serving engine: session-slot KV-cache management, batched decode,
and live session migration.

This is the LM-application face of Beehive: each engine instance is an
"application tile" behind the network stack; sessions are flows (the
flow-hash dispatch pins a session to an engine), and `migrate_out` /
`migrate_in` move a session between engines exactly like the paper's TCP
live migration moves a connection — serialize state, reinstall, flip the
NAT/dispatch table.

Cache layout: stacked (n_units leading axis) with a session axis of size
`max_sessions`; per-session positions drive scatter writes in decode.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model
from repro.models.config import ModelConfig
from repro.sharding import SINGLE, Policy


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_sessions: int = 4,
                 max_seq: int = 128, policy: Policy = SINGLE):
        assert cfg.supports_decode
        self.cfg = cfg
        self.params = params
        self.policy = policy
        self.M = max_sessions
        self.S = max_seq
        self.cache = model.init_cache(cfg, max_sessions, max_seq)
        self.pos = jnp.zeros((max_sessions,), jnp.int32)
        self.used = np.zeros((max_sessions,), bool)
        self.last_tok = jnp.zeros((max_sessions,), jnp.int32)
        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl)

    # ---- session lifecycle -----------------------------------------------
    def has_free_slot(self) -> bool:
        return bool((~self.used).any())

    def release(self, sid: int) -> None:
        """Free slot `sid` (explicit close / LRU eviction).  The cache
        column is left in place — a freed slot's stale entries are
        invisible (attention never reads past `pos`, and `pos` is reset on
        the next install)."""
        self.used[sid] = False
        self.pos = self.pos.at[sid].set(0)
        self.last_tok = self.last_tok.at[sid].set(0)

    def new_session(self, prompt_tokens: np.ndarray,
                    extras: Optional[Dict] = None) -> int:
        """Prefill the prompt into a free slot; returns the session id."""
        free = np.where(~self.used)[0]
        if not len(free):
            raise RuntimeError("no free session slots")
        sid = int(free[0])
        batch = {"tokens": jnp.asarray(prompt_tokens)[None, :]}
        if extras:
            batch.update({k: jnp.asarray(v)[None] for k, v in extras.items()})
        tok, pcache = self._prefill(self.params, batch)
        tok = tok[0]
        P = prompt_tokens.shape[0]
        self._install_cache(sid, pcache, P)
        self.pos = self.pos.at[sid].set(P)
        self.last_tok = self.last_tok.at[sid].set(tok)
        self.used[sid] = True
        return sid

    def _install_cache(self, sid: int, pcache, prompt_len: int):
        """Copy a prefill cache (seq length P) into slot `sid`.

        Alignment: global-attention caches are prefix-aligned (position i at
        index i -> pad right); rolling-window caches keep the newest entry
        last (-> pad left). Recurrent states are O(1)."""
        def put(slot_leaf, new_leaf, left: bool):
            new = jnp.moveaxis(new_leaf, 1, 0)[0]       # (U, T, ...)
            T = new.shape[1]
            gap = slot_leaf.shape[2] - T
            pad = [(0, 0)] * new.ndim
            pad[1] = (gap, 0) if left else (0, gap)
            return slot_leaf.at[:, sid].set(jnp.pad(new, pad))

        def merge(path, slot_leaf, new_leaf):
            names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
            if names[-1] in ("k", "v"):
                i = int(names[0][1:])                   # pattern position
                mixer, _ = self.cfg.entry(self.cfg.pattern[i])
                return put(slot_leaf, new_leaf, left=(mixer == "attn_l"))
            # recurrent states: (U, 1, ...) -> slot (U, M, ...)
            return slot_leaf.at[:, sid].set(jnp.moveaxis(new_leaf, 1, 0)[0])

        self.cache["units"] = jax.tree_util.tree_map_with_path(
            merge, self.cache["units"], pcache["units"])

        for j in range(len(self.cache["rem"])):
            mixer, _ = self.cfg.entry(self.cfg.remainder[j])

            def merge_rem(path, slot_leaf, new_leaf, _mx=mixer):
                names = [getattr(k, "key", getattr(k, "name", ""))
                         for k in path]
                new = new_leaf[0]                   # drop batch dim
                if names[-1] in ("k", "v"):         # (T, KV, hd)
                    gap = slot_leaf.shape[1] - new.shape[0]
                    pad = [(gap, 0) if _mx == "attn_l" else (0, gap)] + \
                        [(0, 0)] * (new.ndim - 1)
                    new = jnp.pad(new, pad)
                return slot_leaf.at[sid].set(new)

            self.cache["rem"][j] = jax.tree_util.tree_map_with_path(
                merge_rem, self.cache["rem"][j], pcache["rem"][j])

    def _prefill_impl(self, params, batch):
        logits, pcache = model.prefill(self.cfg, params, batch, self.policy)
        return model.greedy_token(self.cfg, logits), pcache

    # ---- batched decode ---------------------------------------------------
    def _decode_impl(self, params, cache, tok, pos):
        logits, cache = model.decode_step(self.cfg, params, cache, tok, pos,
                                          self.policy)
        nxt = model.greedy_token(self.cfg, logits)
        return nxt, cache

    def step(self) -> np.ndarray:
        """One decode step for every active session. Returns next tokens."""
        nxt, self.cache = self._decode(self.params, self.cache,
                                       self.last_tok, self.pos)
        self.pos = self.pos + jnp.asarray(self.used, jnp.int32)
        self.last_tok = jnp.where(jnp.asarray(self.used), nxt, self.last_tok)
        return np.asarray(self.last_tok)

    def generate(self, sid: int, n: int) -> List[int]:
        out = []
        for _ in range(n):
            toks = self.step()
            out.append(int(toks[sid]))
        return out

    # ---- live migration (the paper's §6.7, generalized to sessions) -------
    def migrate_out(self, sid: int) -> Dict:
        """Serialize session `sid` (cache column + position + last token)."""
        blob = {
            "units": jax.tree.map(lambda x: x[:, sid], self.cache["units"]),
            "rem": [jax.tree.map(lambda x: x[sid], c)
                    for c in self.cache["rem"]],
            "pos": self.pos[sid],
            "last_tok": self.last_tok[sid],
        }
        self.used[sid] = False
        return blob

    def migrate_in(self, blob: Dict) -> int:
        free = np.where(~self.used)[0]
        if not len(free):
            raise RuntimeError("no free session slots")
        sid = int(free[0])
        self.cache["units"] = jax.tree.map(
            lambda slot, b: slot.at[:, sid].set(b),
            self.cache["units"], blob["units"])
        for j, b in enumerate(blob["rem"]):
            self.cache["rem"][j] = jax.tree.map(
                lambda s, x: s.at[sid].set(x), self.cache["rem"][j], b)
        self.pos = self.pos.at[sid].set(blob["pos"])
        self.last_tok = self.last_tok.at[sid].set(blob["last_tok"])
        self.used[sid] = True
        return sid
