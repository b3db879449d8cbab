"""Cross-device scale-out of the compiled pipeline (paper §5 scale-out).

Composes the two parallelism layers of the sharded dataplane:

  * **intra-device**: RSS replica groups (`core.scaleout.replicate`) fan
    hot tiles out into batched lanes *inside* each shard's compiled scan;
  * **cross-device**: `ShardedStream` wraps `run_stream` in `shard_map`
    over the ``("data",)`` axis of a `launch.mesh.make_mesh_for` mesh, so
    S devices each stream their own row-partition of the frame arena.

Flows are partitioned at the arena-fill boundary — the host-side RSS a
ToR switch or NIC would perform — so shards never exchange traffic and
the per-shard scan lowers with ZERO collectives.  The no-collective /
no-host-callback certificates are checked by ``benchmarks/bench_shard.py``;
per-flow egress is bit-identical to the unsharded reference because each
shard runs the *same* compiled pipeline over the same frames it would see
behind a real RSS front end.

Per-shard management stays in-band: `ShardedConsole` slices one shard's
state view, drives the ordinary `MgmtConsole` against it (LOG_READ /
DROP_READ / GROUP_READ / drain_replica all address that shard's device
tables), and scatters the updated state back.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding

from repro.launch.compat import shard_map
from repro.launch.mesh import make_mesh_for
from repro.net.frames import FrameArena
from repro.obs import host
from repro.sharding import Policy


class ShardedFrameArena:
    """(S, n_batches, batch, max_len) frame store with per-shard
    :class:`FrameArena` views.  The views alias the parent buffers, so
    per-shard `fill` writes land in the one contiguous array that feeds
    `ShardedStream.run_stream` — no per-shard copies."""

    def __init__(self, shards: int, n_batches: int, batch: int,
                 max_len: int):
        self.shards = shards
        self.n_batches = n_batches
        self.batch = batch
        self.max_len = max_len
        self.payload = np.zeros((shards, n_batches, batch, max_len),
                                np.uint8)
        self.length = np.zeros((shards, n_batches, batch), np.int32)
        self._views = [FrameArena.from_buffers(self.payload[s],
                                               self.length[s])
                       for s in range(shards)]

    def shard(self, s: int) -> FrameArena:
        """Shard ``s``'s arena view (writes go to the parent buffers)."""
        return self._views[s]

    @property
    def capacity(self) -> int:
        return self.shards * self.n_batches * self.batch

    def clear(self):
        self.payload[:] = 0
        self.length[:] = 0

    def fill_shards(self, frames_per_shard: Sequence[Sequence[bytes]]):
        """Fill each shard from its own frame list (pre-partitioned).
        Each call is one ``ingress/fill`` span (``repro.obs.host``)."""
        with host.span("ingress/fill") as extra:
            self._fill_shards(frames_per_shard)
            extra["frames"] = sum(len(f) for f in frames_per_shard)

    def _fill_shards(self, frames_per_shard: Sequence[Sequence[bytes]]):
        if len(frames_per_shard) != self.shards:
            raise ValueError(
                f"{len(frames_per_shard)} frame lists for "
                f"{self.shards} shards")
        self.clear()
        for s, frames in enumerate(frames_per_shard):
            self._views[s]._fill(list(frames))

    def fill_rss(self, flows: Dict[int, Sequence[bytes]]):
        """Host-side RSS: partition whole *flows* across shards —
        ``flows`` maps a flow key (e.g. the client port) to that flow's
        frames, and every frame of a flow lands on ``key % shards`` so
        per-flow ordering survives the split, exactly like a hardware
        hash front end.  Returns the per-shard frame counts.  Each call
        is one ``ingress/fill`` span (``repro.obs.host``)."""
        with host.span("ingress/fill") as extra:
            per: List[List[bytes]] = [[] for _ in range(self.shards)]
            for key, frames in flows.items():
                per[key % self.shards].extend(frames)
            self._fill_shards(per)
            counts = [len(p) for p in per]
            extra["frames"] = sum(counts)
        return counts


class ShardedStream:
    """`shard_map` wrapper of a stack's :meth:`run_stream` over the
    ``("data",)`` mesh axis.  State, arena, and outputs all carry a
    leading shard axis; inside each shard the axis has extent 1 and is
    squeezed away, so the per-shard program is the *unmodified* compiled
    pipeline — replica groups, mgmt commits, telemetry and all."""

    def __init__(self, stack, shards: Optional[int] = None, mesh=None):
        self.stack = stack
        self.shards = shards if shards is not None else len(jax.devices())
        self.mesh = mesh if mesh is not None else make_mesh_for(
            self.shards, model_parallel=1)
        self.policy = Policy(dp=("data",), enabled=True)
        spec = self.policy.batch()
        # leading shard axis split over the mesh: shard s lives on device s
        self.sharding = NamedSharding(self.mesh, spec)

        def body(state, payloads, lengths):
            st = jax.tree.map(lambda x: x[0], state)
            st, outs = stack.run_stream(st, payloads[0], lengths[0])
            return (jax.tree.map(lambda x: x[None], st),
                    jax.tree.map(lambda x: x[None], outs))

        self._sharded = shard_map(body, mesh=self.mesh,
                                  in_specs=(spec, spec, spec),
                                  out_specs=(spec, spec))

    def init_state(self):
        """One replica of the stack state per shard (leading S axis),
        each placed straight on its shard's device."""
        st = self.stack.init_state()
        return jax.tree.map(
            lambda x: jax.device_put(
                np.broadcast_to(np.asarray(x), (self.shards,) + x.shape),
                self.sharding), st)

    def make_arena(self, n_batches: int, batch: int,
                   max_len: int) -> ShardedFrameArena:
        return ShardedFrameArena(self.shards, n_batches, batch, max_len)

    def run_stream(self, state, payloads, lengths):
        """All shards stream their (N, B, L) partition under one
        dispatch.  Returns (state', outs) with leading shard axes."""
        return self._sharded(state, jax.device_put(payloads, self.sharding),
                             jax.device_put(lengths, self.sharding))

    def stream_fn(self):
        """Jitted entry point with the state carry donated, matching the
        single-device `stack.stream_fn()` discipline."""
        return jax.jit(self._sharded, donate_argnums=(0,))


class ShardedConsole:
    """Per-shard in-band management over a `ShardedStream` state.

    Slices shard ``s``'s state view, runs the ordinary `MgmtConsole`
    operation against it (the command frames traverse that shard's
    compiled pipeline), and scatters the updated state back into the
    stacked tree — so `LOG_READ` / `DROP_READ` / `GROUP_READ` address one
    shard's device tables, and `drain_replica` drains one shard's RSS
    lane without touching its siblings."""

    def __init__(self, stack, shards: int):
        from repro.mgmt.console import MgmtConsole
        self.console = MgmtConsole(stack)
        self.shards = shards

    def on_shard(self, state, s: int, method: str, *args, **kwargs):
        """Run one MgmtConsole method against shard ``s``."""
        if not 0 <= s < self.shards:
            raise IndexError(f"shard {s} out of range "
                             f"(0..{self.shards - 1})")
        view = jax.tree.map(lambda x: x[s], state)
        view, r = getattr(self.console, method)(view, *args, **kwargs)
        state = jax.tree.map(lambda full, new: full.at[s].set(new),
                             state, view)
        return state, r

    # the per-shard addressing surface the operator console uses --------
    def read_counters(self, state, shard: int, tile: str, age: int = 0):
        return self.on_shard(state, shard, "read_counters", tile, age)

    def read_drops(self, state, shard: int, tile: str):
        return self.on_shard(state, shard, "read_drops", tile)

    def read_group(self, state, shard: int, group: str):
        return self.on_shard(state, shard, "read_group", group)

    def drain_replica(self, state, shard: int, group: str, replica: int):
        return self.on_shard(state, shard, "drain_replica", group,
                             replica)

    def restore_replica(self, state, shard: int, group: str,
                        replica: int):
        return self.on_shard(state, shard, "restore_replica", group,
                             replica)

    def dump_counters(self, state, age: int = 0
                      ) -> Tuple[Dict, Dict[int, Dict[str, Dict]]]:
        """Every shard's per-tile counter rows: {shard: {tile: row}}."""
        from repro.core import control
        out: Dict[int, Dict[str, Dict]] = {}
        con = self.console
        tiles = list(con.node_ids)
        for s in range(self.shards):
            view = jax.tree.map(lambda x: x[s], state)
            view, resps = con.roundtrip(view, [
                (control.OP_LOG_READ, 0, con.node_ids[t], age, 0)
                for t in tiles])
            state = jax.tree.map(lambda full, new: full.at[s].set(new),
                                 state, view)
            out[s] = {t: r["row"] for t, r in zip(tiles, resps)
                      if r["status"] == 1}
        return state, out
