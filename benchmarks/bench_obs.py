"""Observability overhead: pull + push telemetry vs telemetry-only.

The tentpole's cost claim: the device-resident observability layer rides
the same `run_stream` scan as the dataplane with no host callbacks — so
the only acceptable price is a small amount of extra on-device
arithmetic.  This bench measures it across three configs:

  * **baseline** — `UdpStack(..., with_obs=False)`: the full production
    pipeline with fused per-tile telemetry counters, exactly the
    pre-observability streamed path.
  * **obs** — the default stack with the recorder enabled at the
    production sampling rate (1 in 2**6 frames) and histograms
    accumulating every frame of every batch.
  * **push** — obs plus the whole push side: `int_mirror` packing
    postcards at 1/64, the series ring closing windows, and the SLO
    watchdog evaluating one installed rule per batch.

All run identical UDP-echo windows through donated `run_stream`
dispatches.  Appends a trajectory entry to ``BENCH_obs.json`` and gates
(`make bench-obs` fails otherwise):

  * obs AND push streamed time within 10% of the telemetry-only
    baseline, and
  * zero host callbacks/transfers in either scanned region.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import append_trajectory, row
from repro.apps import echo
from repro.launch.certify import assert_no_host_callbacks
from repro.launch.compile_cache import enable_compile_cache
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack, udp_topology
from repro.obs import postcard, series, slo

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_obs.json")
OVERHEAD_GATE = 0.10


def _enable_recorder(state, shift: int = 6):
    """Flip the runtime sampling knobs directly in state (what TRACE_SET
    stages through the management plane — the bench needs no mgmt port)."""
    obs = dict(state["telemetry"]["obs"])
    obs["ctrl"] = {"enable": jnp.ones((), jnp.int32),
                   "shift": jnp.full((), shift, jnp.int32)}
    state = dict(state)
    state["telemetry"] = dict(state["telemetry"])
    state["telemetry"]["obs"] = obs
    return state


def _enable_push(state, stack, shift: int = 6):
    """Recorder at 1/2**shift (gates postcard packing too), series window
    length, and one live SLO rule — what SLO_SET/TRACE_SET would stage."""
    state = _enable_recorder(state, shift)
    ser = dict(state["telemetry"]["series"])
    ser["win_len"] = jnp.asarray(8, jnp.int32)
    state["telemetry"]["series"] = ser
    node = stack.pipeline.order.index("ip_rx")
    s = dict(state["slo"])
    s["metric"] = s["metric"].at[0].set(series.M_DROPS)
    s["node"] = s["node"].at[0].set(node)
    s["thr_raise"] = s["thr_raise"].at[0].set(1 << 20)
    s["thr_clear"] = s["thr_clear"].at[0].set(1 << 19)
    s["enabled"] = s["enabled"].at[0].set(1)
    state["slo"] = s
    return state


def _push_stack():
    apps = [echo.make(port=7)]
    topo = udp_topology(apps)
    postcard.bind_mirror(topo, collector_ip=IP_C)
    slo.bind_watchdog(topo, collector_ip=IP_C)
    return UdpStack(apps, IP_S, topo=topo)


def measure(n_batches: int = 64, batch: int = 16, frame_payload: int = 64,
            repeats: int = 7, shift: int = 6):
    fr = F.udp_rpc_frame(IP_C, IP_S, 5000, 7,
                         rpc.np_frame(rpc.MSG_ECHO, 0,
                                      b"x" * frame_payload))
    frames = [fr] * batch
    width = len(fr) + 64
    arena = F.FrameArena(n_batches, batch, width)
    arena.fill(frames * n_batches)
    n_pkts = n_batches * batch

    def timed_window(stack, st, stream):
        arena.fill(frames * n_batches)
        t0 = time.perf_counter()
        st, outs = stream(st, jnp.asarray(arena.payload),
                          jnp.asarray(arena.length))
        jax.block_until_ready(outs)
        return st, time.perf_counter() - t0

    def build_baseline():
        return UdpStack([echo.make(port=7)], IP_S, with_obs=False)

    def build_obs():
        return UdpStack([echo.make(port=7)], IP_S)

    results = {}
    for name, build, armfn in (("baseline", build_baseline, None),
                               ("obs", build_obs, _enable_recorder),
                               ("push", _push_stack, _enable_push)):
        stack = build()
        st = stack.init_state()
        if armfn is not None:
            st = (armfn(st, stack, shift) if armfn is _enable_push
                  else armfn(st, shift))
            assert_no_host_callbacks(
                stack.run_stream, st,
                jnp.asarray(arena.payload), jnp.asarray(arena.length))
        stream = stack.stream_fn()
        st, _ = timed_window(stack, st, stream)        # compile + warm
        ts = []
        for _ in range(repeats):
            st, t = timed_window(stack, st, stream)
            ts.append(t)
        results[name] = min(ts)

    t_b, t_o, t_p = results["baseline"], results["obs"], results["push"]
    return {
        "n_batches": n_batches, "batch": batch, "frame_bytes": len(fr),
        "sample_shift": shift, "packets_per_window": n_pkts,
        "baseline_us": t_b * 1e6, "obs_us": t_o * 1e6, "push_us": t_p * 1e6,
        "baseline_pps": n_pkts / t_b, "obs_pps": n_pkts / t_o,
        "push_pps": n_pkts / t_p,
        "overhead": t_o / t_b - 1.0,
        "overhead_push": t_p / t_b - 1.0,
    }


def run():
    r = measure()
    out = [row("obs_udp_echo_baseline",
               r["baseline_us"] / r["packets_per_window"],
               f"cpu={r['baseline_pps']:.0f}pps"),
           row("obs_udp_echo_recorded",
               r["obs_us"] / r["packets_per_window"],
               f"cpu={r['obs_pps']:.0f}pps "
               f"overhead={100 * r['overhead']:.1f}%"),
           row("obs_udp_echo_push",
               r["push_us"] / r["packets_per_window"],
               f"cpu={r['push_pps']:.0f}pps "
               f"overhead={100 * r['overhead_push']:.1f}%")]
    append_trajectory(OUT_PATH, r)
    worst = max(r["overhead"], r["overhead_push"])
    if worst > OVERHEAD_GATE:
        raise RuntimeError(
            f"observability overhead {100 * worst:.1f}% exceeds the "
            f"{100 * OVERHEAD_GATE:.0f}% gate (recorder at "
            f"1/{2 ** r['sample_shift']} sampling + histograms + "
            f"postcards/series/watchdog)")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
