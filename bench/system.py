"""The system under test as the harness drives it: a stack on one chip
(``Single``) or a ``ShardedStream`` over several (``Sharded``).

Both expose the same few calls, each a call into the program's own API:
``fill`` (``FrameArena.fill`` / ``ShardedFrameArena.fill_rss``), ``put``
(``jax.device_put``), ``step`` (the compiled, state-donating
``stream_fn()``), and ``readback`` of what the stack transmits.  Arrays
that come back carry a leading shard axis in both cases.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

READ_KEYS = ("tx_payload", "tx_len", "alive")


class Single:
    shards = 1

    def __init__(self, stack, app: str, n_batches: int, batch: int,
                 width: int):
        from repro.net import frames
        self.stack = stack
        self.app = app
        self.shape = (n_batches, batch, width)
        self._arena = frames.FrameArena
        self.step = stack.stream_fn()
        self.pipeline = stack.pipeline

    def init_state(self):
        return self.stack.init_state()

    def new_arena(self):
        return self._arena(*self.shape)

    def fill_input(self, frames: List[bytes], client_port, rows):
        """What ``fill`` takes for a window whose one shard's rows hold
        the requests ``rows[0]`` (-1: empty)."""
        return [frames[i] for i in rows[0] if i >= 0]

    def fill(self, arena, window):
        arena.fill(window)

    def put(self, arena):
        return jax.device_put(arena.payload), jax.device_put(arena.length)

    def readback(self, outs) -> Dict[str, np.ndarray]:
        got = {k: np.asarray(outs[k])[None] for k in READ_KEYS}
        got["served"] = np.asarray(outs["info"][self.app])[None]
        return got

    def shard_states(self, state) -> list:
        return [jax.device_get(state)]


class Sharded:
    def __init__(self, stack, app: str, shards: int, n_batches: int,
                 batch: int, width: int):
        from repro.net.shard import ShardedStream
        self.stack = stack
        self.app = app
        self.shards = shards
        self.shape = (n_batches, batch, width)
        self.ss = ShardedStream(stack, shards=shards)
        self.step = self.ss.stream_fn()
        self.pipeline = stack.pipeline

    def init_state(self):
        return self.ss.init_state()

    def new_arena(self):
        return self.ss.make_arena(*self.shape)

    def fill_input(self, frames: List[bytes], client_port, rows):
        """``fill_rss`` takes whole flows keyed by client port; the rows
        of each shard must already hold each flow's frames together, in
        the order the flows first appear."""
        flows: Dict[int, List[bytes]] = {}
        for s in range(self.shards):
            for i in rows[s]:
                if i >= 0:
                    flows.setdefault(int(client_port[i]), []).append(
                        frames[i])
        return flows

    def fill(self, arena, window):
        arena.fill_rss(window)

    def put(self, arena):
        return (jax.device_put(arena.payload, self.ss.sharding),
                jax.device_put(arena.length, self.ss.sharding))

    def readback(self, outs) -> Dict[str, np.ndarray]:
        got = {k: np.asarray(outs[k]) for k in READ_KEYS}
        got["served"] = np.asarray(outs["info"][self.app])
        return got

    def shard_states(self, state) -> list:
        host = jax.device_get(state)
        return [jax.tree.map(lambda x: x[s], host)
                for s in range(self.shards)]
