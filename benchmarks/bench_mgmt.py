"""Management-plane contention check (paper §3.6: control runs on its own
NoC and "never contends" with the dataplane).

Measures the compiled UDP echo pipeline's per-batch cost on a
management-bound stack three ways: pure data traffic, data with 1%
management commands interleaved (the paper's operating regime), and
management-only batches (ack latency).  The derived column reports the 1%
interleave overhead vs pure data — the regression check: it should stay
within noise, since management frames ride the same batch and the ctrl
NoC adds no dataplane stages."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from benchmarks.common import append_trajectory, row, time_call
from repro.apps import echo
from repro.core import control
from repro.launch.compile_cache import enable_compile_cache
from repro.mgmt.console import command_frame
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
MGMT_PORT = 9909
BATCH = 100          # 1 management frame = 1% of the batch
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_mgmt.json")


def _batches():
    data = [F.udp_rpc_frame(IP_C, IP_S, 5000 + i, 7,
                            rpc.np_frame(rpc.MSG_ECHO, i, b"x" * 64))
            for i in range(BATCH)]
    mgmt = command_frame(IP_C, IP_S, 5999, MGMT_PORT,
                         control.OP_LOG_READ, a=0, b=0, req_id=1)
    mixed = data[:BATCH - 1] + [mgmt]
    mgmt_only = [command_frame(IP_C, IP_S, 5999, MGMT_PORT,
                               control.OP_VERSION, req_id=i)
                 for i in range(BATCH)]
    out = {}
    for name, frames in (("pure", data), ("mixed", mixed),
                         ("mgmt", mgmt_only)):
        p, l = F.to_batch(frames, 256)
        out[name] = (jnp.asarray(p), jnp.asarray(l))
    return out

def run():
    stack = UdpStack([echo.make(port=7)], IP_S, mgmt_port=MGMT_PORT)
    batches = _batches()
    fn = jax.jit(lambda s, p, l: stack.rx_tx(s, p, l))

    us = {}
    for name, (p, l) in batches.items():
        us[name] = time_call(fn, stack.init_state(), p, l, warmup=3,
                             iters=21)

    overhead = (us["mixed"] / us["pure"] - 1) * 100
    out = [row("mgmt_dataplane_pure", us["pure"] / BATCH,
               f"batch={BATCH} baseline"),
           row("mgmt_interleave_1pct", us["mixed"] / BATCH,
               f"overhead={overhead:+.1f}% (claim: control never "
               f"contends)"),
           row("mgmt_ack_batch", us["mgmt"] / BATCH,
               "management-only acks")]
    append_trajectory(OUT_PATH, {
        "batch": BATCH, "pure_us": us["pure"], "mixed_us": us["mixed"],
        "mgmt_only_us": us["mgmt"], "interleave_overhead_pct": overhead})
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
