"""Paper Figure 7: TCP send/receive goodput vs payload size.

RX: batches of in-order data segments through the jitted engine —
per-batch (one dispatch per batch) and streamed (N batches under one
`lax.scan`, the run_stream execution shape).  TX: app_send + tx_emit
segment generation.  Derived: TPU-projected segments/s and goodput from
compiled HBM traffic."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import (append_trajectory, hlo_traffic, row,
                               time_call)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import HBM_BW
from repro.net import eth, frames as F, ipv4, tcp

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
BATCH = 32
STREAM_BATCHES = 16
SIZES = (64, 512, 1460)
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_tcp.json")


def _rx_ready(conn, size):
    frames = []
    seq = 5001
    for i in range(BATCH):
        frames.append(F.tcp_eth_frame(IP_C, IP_S, 4000, 80, seq=seq,
                                      ack=0, flags=tcp.ACK | tcp.PSH,
                                      payload=b"x" * size))
        seq += size
    payload, length = F.to_batch(frames, size + 80)
    return jnp.asarray(payload), jnp.asarray(length)


def _rx_fn(conn, payload, length):
    p, l, m = eth.parse(payload, length)
    p, l, m2, ok = ipv4.parse(p, l)
    m.update(m2)
    data, dlen, m = tcp.parse_segment(p, l, m)
    return tcp.rx_batch(conn, data, dlen, m)


def run():
    out = []
    traj = {}
    for size in SIZES:
        conn = tcp.init(max_conns=4, rx_buf=BATCH * size + 4096,
                        local_ip=IP_S)
        # establish
        syn = F.tcp_eth_frame(IP_C, IP_S, 4000, 80, seq=5000, ack=0,
                              flags=tcp.SYN)
        p0, l0 = F.to_batch([syn], size + 80)
        conn, r = _rx_fn(conn, jnp.asarray(p0), jnp.asarray(l0))
        iss = int(r["tcp_seq"][0])
        ackf = F.tcp_eth_frame(IP_C, IP_S, 4000, 80, seq=5001, ack=iss + 1,
                               flags=tcp.ACK)
        p1, l1 = F.to_batch([ackf], size + 80)
        conn, _ = _rx_fn(conn, jnp.asarray(p1), jnp.asarray(l1))

        p, l = _rx_ready(conn, size)
        fn = jax.jit(_rx_fn)
        us = time_call(fn, conn, p, l)
        w = hlo_traffic(_rx_fn, conn, p, l)
        proj_sps = HBM_BW / max(w.hbm_bytes / BATCH, 1)
        proj_gbps = proj_sps * size * 8 / 1e9
        cpu_sps = BATCH / (us / 1e6)
        out.append(row(f"fig7_tcp_rx_{size}B", us / BATCH,
                       f"proj={min(proj_gbps, 100.0):.1f}Gbps "
                       f"cpu={cpu_sps:.0f}segs"))

        # streamed RX: the same segment batch scanned STREAM_BATCHES
        # times device-resident (engine state as the scan carry)
        sfn = jax.jit(lambda c, pp, ll: jax.lax.scan(
            lambda cc, xs: _rx_fn(cc, xs[0], xs[1]), c, (pp, ll)))
        ps = jnp.stack([p] * STREAM_BATCHES)
        ls = jnp.stack([l] * STREAM_BATCHES)
        us_s = time_call(sfn, conn, ps, ls)
        n_segs = STREAM_BATCHES * BATCH
        stream_sps = n_segs / (us_s / 1e6)
        out.append(row(f"fig7_tcp_rx_{size}B_stream", us_s / n_segs,
                       f"cpu={stream_sps:.0f}segs "
                       f"speedup={stream_sps / cpu_sps:.2f}x"))

        # TX: stage + emit one MSS segment
        data = jnp.zeros((size,), jnp.uint8)
        conn2, _ = tcp.app_send(conn, 0, data, size)
        tx = jax.jit(lambda c: tcp.tx_emit(c, 0, mss=1460))
        us_tx = time_call(tx, conn2)
        out.append(row(f"fig7_tcp_tx_{size}B", us_tx,
                       f"cpu={1e6/us_tx:.0f}segs/s"))
        traj[f"rx_sps_{size}B"] = cpu_sps
        traj[f"rx_stream_sps_{size}B"] = stream_sps
        traj[f"tx_us_{size}B"] = us_tx
    append_trajectory(OUT_PATH, traj)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
