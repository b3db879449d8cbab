"""TCP goodput under loss (the paper's missing experiment: §4.4 ships
without congestion control, so the stack was never measured on a lossy
fabric).

Drives one server->client transfer through the deterministic netem link
at 0% / 0.1% / 1% i.i.d. loss with the NewReno engine and reports
goodput (payload bytes per emulated tick), the fraction of lossless
goodput retained, and the p99 / max recovery gap (ticks between
consecutive in-order advances at the client — the recovery-latency tail).

Gate (ISSUE 3 acceptance): at 1% loss the transfer must complete with
zero permanent stalls and sustain >= 20% of the lossless goodput.

Appends a trajectory entry to ``BENCH_tcp_loss.json`` (history across
PRs, like the other BENCH_*.json files)."""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks.common import append_trajectory, row
from repro.launch.compile_cache import enable_compile_cache
from repro.net import frames as F
from repro.net.stack import TcpStack
from repro.netem import Link, LinkConfig, LinuxTcpClient, StackEndpoint, \
    run_transfer

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_tcp_loss.json")
MSS = 1024
PAYLOAD_BYTES = 32768
LOSS_RATES = (0.0, 0.001, 0.01)
MAX_TICKS = 20000


def _transfer(srv, loss, seed=11):
    srv.reset()
    client = LinuxTcpClient(IP_C, IP_S)
    l_cs = Link(LinkConfig(delay=2, seed=seed))
    l_sc = Link(LinkConfig(delay=2, loss=loss, seed=seed + 1))
    payload = bytes(np.random.default_rng(3).integers(
        0, 256, PAYLOAD_BYTES, dtype=np.uint8))
    t0 = time.perf_counter()
    stats = run_transfer(srv, client, l_cs, l_sc, payload,
                         max_ticks=MAX_TICKS)
    return stats, (time.perf_counter() - t0) * 1e6


def run():
    stack = TcpStack(IP_S, max_conns=4, cc_policy="newreno",
                     options={"tcp_tx_buf": PAYLOAD_BYTES + 4096,
                              "mss": MSS})
    srv = StackEndpoint(stack, mss=MSS, rx_width=96, burst=8)
    _transfer(srv, 0.0)                      # jit warmup

    out = []
    base = None
    traj = {"payload_bytes": PAYLOAD_BYTES, "mss": MSS}
    for loss in LOSS_RATES:
        stats, us = _transfer(srv, loss)
        if not stats.complete:
            raise RuntimeError(
                f"permanent stall at {loss:.1%} loss: {stats}")
        if base is None:
            base = stats.goodput
        rel = stats.goodput / base
        cc = srv.state["conn"]["cc"]
        retx = int(cc["retx_fast"][0]) + int(cc["retx_timer"][0])
        traj[f"loss_{loss:g}"] = {
            "us": us, "goodput_B_per_tick": stats.goodput, "rel": rel,
            "p99_gap": float(stats.p99_gap), "max_gap": int(stats.max_gap),
            "retx": retx}
        out.append(row(
            f"tcp_loss_{loss:g}", us,
            f"goodput={stats.goodput:.0f}B/tick rel={rel:.0%} "
            f"p99_gap={stats.p99_gap:.0f}t max_gap={stats.max_gap}t "
            f"retx={retx}"))
        if loss == 0.01 and rel < 0.20:
            raise RuntimeError(
                f"1% loss sustains only {rel:.0%} of lossless goodput "
                f"(gate: >= 20%)")
    append_trajectory(OUT_PATH, traj)

    # harness RX path: per-batch dispatch loop vs arena-streamed push
    # (stream=False forces the pre-streaming per-chunk Python loop; same
    # links/seeds/payload, but the streamed push services a whole burst
    # before emitting retransmits — recovery work under loss can differ
    # slightly, so read this as a harness-cost indicator, not a
    # controlled A/B of the engine)
    srv_b = StackEndpoint(stack, mss=MSS, rx_width=96, burst=8,
                          stream=False)
    _transfer(srv_b, 0.01)                   # jit warmup
    _, us_b = _transfer(srv_b, 0.01)
    _, us_s = _transfer(srv, 0.01)
    out.append(row("tcp_loss_harness_stream", us_s,
                   f"per_batch={us_b:.0f}us streamed={us_s:.0f}us "
                   f"speedup={us_b / max(us_s, 1):.2f}x"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
