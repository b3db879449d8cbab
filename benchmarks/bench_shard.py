"""Sharded-dataplane gate: shard_map scale-out of the compiled pipeline.

Runs the RSS-replicated UDP echo stack (2 udp_rx lanes behind a
flow-hash dispatch) both unsharded and S-way sharded, and certifies the
scale-out claim three ways:

  * **bit-identity** — every shard's streamed egress equals the
    unsharded reference run over the same frame partition;
  * **no collectives** — the sharded HLO contains no all-reduce /
    all-gather / collective-permute / all-to-all: shards are fully
    independent, so per-device throughput is preserved under scale-out;
  * **zero host callbacks** — the per-shard scanned region never touches
    the host (same jaxpr walk as the stream/obs gates).

Gate: the *certified projected aggregate* throughput on S devices must
be >= 4x the single-device baseline.  On a CPU box every "device" is a
forced host-platform device on ONE physical core, so sharded wall time
cannot beat the baseline; the certificates above are exactly what makes
the projection sound (S independent, collective-free, host-free programs
run concurrently on S real devices), so the projection is

    projected_pps = total_packets / (sharded_wall / S)

i.e. per-shard work divided by per-shard time, times S.  The measured
wall figures are reported and recorded alongside it.

Devices: on an accelerator the run uses every device of this process
(at least 2) in-process — a chip belongs to one process, so no child may
need it.  On the CPU it re-launches itself on a forced 8-device host mesh
via `repro.launch.hostmesh` (the child is pinned to the CPU).  A run that
cannot get its devices fails.  APPENDS to BENCH_shard.json.
"""
from __future__ import annotations

import json
import os
import time

from benchmarks.common import append_trajectory, row
from repro.launch.compile_cache import enable_compile_cache

HOST_SHARDS = 8
N_BATCHES = 8
BATCH = 32
MAX_LEN = 256
MIN_SPEEDUP = 4.0
_ROOT = os.path.join(os.path.dirname(__file__), "..")
OUT_PATH = os.path.join(_ROOT, "BENCH_shard.json")


def _make_stack():
    from repro.apps import echo
    from repro.net import frames as F
    from repro.net.stack import UdpStack, replicated_udp_topology
    apps = [echo.make(port=7)]
    topo = replicated_udp_topology(apps, n_rx=2, policy="flow_hash")
    return UdpStack(apps, F.ip("10.0.0.1"), topo=topo, mgmt_port=9909)


def _wall(fn, state, p, l, iters=3):
    import jax
    state, outs = fn(state, p, l)           # compile + warm
    jax.block_until_ready(outs)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        state, outs = fn(state, p, l)
        jax.block_until_ready(outs)
        best = min(best, time.perf_counter() - t0)
    return best


def measure(shards: int) -> dict:
    """Certify and time the S-way sharded stream on this process's
    devices; raises on any failed certificate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.certify import assert_no_host_callbacks
    from repro.net import frames as F, rpc
    from repro.net.shard import ShardedStream

    ip_s = F.ip("10.0.0.1")
    stack = _make_stack()
    ss = ShardedStream(stack, shards=shards)
    arena = ss.make_arena(N_BATCHES, BATCH, MAX_LEN)

    # one flow per client port; whole flows land on one shard (host RSS)
    per_shard = N_BATCHES * BATCH
    flows = {}
    for f in range(shards * 16):
        flows[5000 + f] = [
            F.udp_rpc_frame(F.ip(f"10.0.0.{2 + f % 50}"), ip_s, 5000 + f, 7,
                            rpc.np_frame(rpc.MSG_ECHO, i, b"x" * 64))
            for i in range(per_shard // 16)]
    counts = arena.fill_rss(flows)
    if any(c != per_shard for c in counts):
        raise RuntimeError(f"uneven RSS partition: {counts}")
    total = shards * per_shard

    # zero host callbacks in the per-shard scanned region
    assert_no_host_callbacks(stack.run_stream, stack.init_state(),
                             jnp.asarray(arena.payload[0]),
                             jnp.asarray(arena.length[0]))

    # no cross-shard collectives in the sharded HLO
    p = jax.device_put(arena.payload, ss.sharding)
    l = jax.device_put(arena.length, ss.sharding)
    hlo = jax.jit(ss._sharded).lower(ss.init_state(), p, l).compile() \
        .as_text()
    found = [b for b in ("all-reduce", "all-gather", "collective-permute",
                         "all-to-all") if b in hlo]
    if found:
        raise RuntimeError(f"cross-shard collectives in sharded HLO: "
                           f"{found}")

    # per-shard egress is bit-identical to the unsharded reference
    _, outs = ss.run_stream(ss.init_state(), p, l)
    outs = jax.tree.map(np.asarray, outs)
    for s in range(shards):
        ref_stack = _make_stack()
        _, ref = ref_stack.run_stream(ref_stack.init_state(),
                                      jnp.asarray(arena.payload[s]),
                                      jnp.asarray(arena.length[s]))
        for k in ("tx_payload", "tx_len", "alive"):
            if not np.array_equal(np.asarray(ref[k]), outs[k][s]):
                raise RuntimeError(f"shard {s} {k} differs from the "
                                   f"unsharded reference")
    served = int(outs["alive"].sum())

    # single-device baseline: one stack streams the ENTIRE workload
    base_stack = _make_stack()
    flat_p = jnp.asarray(arena.payload.reshape(shards * N_BATCHES, BATCH,
                                               MAX_LEN))
    flat_l = jnp.asarray(arena.length.reshape(shards * N_BATCHES, BATCH))
    t_base = _wall(base_stack.stream_fn(), base_stack.init_state(),
                   flat_p, flat_l)
    t_shard = _wall(ss.stream_fn(), ss.init_state(), p, l)

    base_pps = total / t_base
    proj_pps = total / (t_shard / shards)
    return {
        "platform": jax.devices()[0].platform, "shards": shards,
        "total_packets": total, "served": served,
        "base_wall_s": t_base, "shard_wall_s": t_shard,
        "base_pps": base_pps, "shard_wall_pps": total / t_shard,
        "projected_aggregate_pps": proj_pps,
        "projected_speedup": proj_pps / base_pps,
    }


def _measure_on_host_mesh() -> dict:
    from repro.launch import hostmesh
    out = hostmesh.run_script(
        "import json\n"
        "from benchmarks.bench_shard import measure\n"
        f"print('RESULT ' + json.dumps(measure({HOST_SHARDS})))\n",
        devices=HOST_SHARDS, timeout=1800, cwd=_ROOT)
    if hostmesh.UNAVAILABLE in out.stdout:
        raise RuntimeError(f"cannot force {HOST_SHARDS} host devices: "
                           f"{out.stdout.strip()}")
    if out.returncode != 0:
        raise RuntimeError(f"bench_shard subprocess failed:\n"
                           f"{out.stderr[-3000:]}")
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def run():
    import jax
    if jax.default_backend() == "cpu":
        r = _measure_on_host_mesh()
    else:
        if jax.device_count() < 2:
            raise RuntimeError(f"bench_shard needs at least 2 devices, "
                               f"found {jax.device_count()}")
        r = measure(jax.device_count())

    rows = [
        row("shard_baseline_1dev",
            r["base_wall_s"] * 1e6 / r["total_packets"],
            f"{r['platform']}={r['base_pps']:.0f}pps"),
        row(f"shard_scaleout_{r['shards']}dev",
            r["shard_wall_s"] * 1e6 / r["total_packets"],
            f"proj={r['projected_aggregate_pps']:.0f}pps "
            f"wall={r['shard_wall_pps']:.0f}pps "
            f"speedup={r['projected_speedup']:.2f}x "
            f"(certified: no collectives, no callbacks, bit-identical)"),
    ]
    append_trajectory(OUT_PATH, r)
    if r["projected_speedup"] < MIN_SPEEDUP:
        raise RuntimeError(
            f"certified aggregate is only {r['projected_speedup']:.2f}x "
            f"the single-device baseline (gate: >= {MIN_SPEEDUP}x)")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
