"""Streamed vs per-batch executor throughput (the streaming-executor
tentpole metric; first point in the perf trajectory).

Per-batch baseline = the pre-streaming harness shape, per batch: a fresh
``to_batch`` pack (numpy allocation), a host->device transfer, one jitted
``rx_tx`` dispatch, and a host sync on the result — what every benchmark
and the netem tick loop paid before `run_stream` existed.  Streamed = an
in-place `FrameArena` refill + ONE donated `run_stream` dispatch for the
whole window + one sync.

Appends a trajectory entry to ``BENCH_stream.json`` (history across PRs,
like BENCH_rpc_tail.json) and gates: streamed UDP echo CPU pps must be
>= 3x the per-batch baseline (`make bench-stream` fails otherwise)."""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_trajectory, row
from repro.apps import echo
from repro.launch.compile_cache import enable_compile_cache
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_stream.json")


def measure(n_batches: int = 64, batch: int = 16, frame_payload: int = 64,
            repeats: int = 5):
    """Returns {per_batch_pps, streamed_pps, speedup, ...} for one config.
    Telemetry stays ON — this is the full production pipeline, counters
    included."""
    stack = UdpStack([echo.make(port=7)], IP_S)
    fr = F.udp_rpc_frame(IP_C, IP_S, 5000, 7,
                         rpc.np_frame(rpc.MSG_ECHO, 0,
                                      b"x" * frame_payload))
    frames = [fr] * batch
    width = len(fr) + 64
    arena = F.FrameArena(n_batches, batch, width)
    arena.fill(frames * n_batches)

    fn = jax.jit(stack.rx_tx, donate_argnums=(0,))
    stream = stack.stream_fn()
    n_pkts = n_batches * batch

    def per_batch(st):
        t0 = time.perf_counter()
        for _ in range(n_batches):
            p, l = F.to_batch(frames, width)       # fresh pack per batch
            st, q, ql, alive, info = fn(st, jnp.asarray(p),
                                        jnp.asarray(l))
            np.asarray(ql)                         # per-batch host sync
        return st, time.perf_counter() - t0

    def streamed(st):
        t0 = time.perf_counter()
        arena.fill(frames * n_batches)             # in-place refill
        st, outs = stream(st, jnp.asarray(arena.payload),
                          jnp.asarray(arena.length))
        jax.block_until_ready(outs)
        return st, time.perf_counter() - t0

    st_b, _ = per_batch(stack.init_state())        # compile + warm
    st_s, _ = streamed(stack.init_state())
    ts_b, ts_s = [], []
    for _ in range(repeats):
        st_b, t = per_batch(st_b)
        ts_b.append(t)
        st_s, t = streamed(st_s)
        ts_s.append(t)
    t_b, t_s = min(ts_b), min(ts_s)
    return {
        "n_batches": n_batches, "batch": batch,
        "frame_bytes": len(fr), "packets_per_window": n_pkts,
        "per_batch_us": t_b * 1e6, "streamed_us": t_s * 1e6,
        "per_batch_pps": n_pkts / t_b, "streamed_pps": n_pkts / t_s,
        "speedup": t_b / t_s,
    }


def run():
    r = measure()
    out = [row("stream_udp_echo_per_batch",
               r["per_batch_us"] / r["packets_per_window"],
               f"cpu={r['per_batch_pps']:.0f}pps"),
           row("stream_udp_echo_streamed",
               r["streamed_us"] / r["packets_per_window"],
               f"cpu={r['streamed_pps']:.0f}pps "
               f"speedup={r['speedup']:.2f}x")]
    append_trajectory(OUT_PATH, r)       # flat entry, same shape as the
    # other BENCH_*.json trajectories (older points nested it under
    # "udp_echo")
    if r["speedup"] < 3.0:
        raise RuntimeError(
            f"streamed UDP echo is only {r['speedup']:.2f}x the per-batch "
            f"baseline (gate: >= 3x)")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
