"""Paper Figure 9 / Table 3: VR witness latency & throughput, 1-4 shards.

Measured: CPU requests/s through the stack with port-match shard dispatch,
plus per-request service latency.  Derived: TPU projection from compiled
traffic and the NoC chain latency (the witness's reply latency floor)."""
from __future__ import annotations

import struct

import jax
import jax.numpy as jnp

from benchmarks.common import hlo_traffic, row, time_call
from repro.apps import vr_witness
from repro.core.noc import chain_latency_ns
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import HBM_BW
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
REQS = 64


def _frames(n_shards):
    frames = []
    per = REQS // n_shards
    for s in range(n_shards):
        for i in range(per):
            body = struct.pack("!IIII", vr_witness.OP_PREPARE, 0, i + 1, 7)
            frames.append(F.udp_rpc_frame(
                IP_C, IP_S, 5000 + i, 9100 + s,
                rpc.np_frame(rpc.MSG_VR_PREPARE, i, body)))
    return F.to_batch(frames, 256)


def run():
    out = []
    for shards in (1, 2, 3, 4):
        stack = UdpStack([vr_witness.make(base_port=9100, n_shards=shards)],
                         IP_S, with_telemetry=False)
        state = stack.init_state()
        payload, length = _frames(shards)
        p, l = jnp.asarray(payload), jnp.asarray(length)
        fn = jax.jit(lambda s, pp, ll: stack.rx_tx(s, pp, ll))
        us = time_call(fn, state, p, l)
        w = hlo_traffic(lambda s, pp, ll: stack.rx_tx(s, pp, ll), state, p, l)
        proj_rps = HBM_BW / max(w.hbm_bytes / REQS, 1)
        out.append(row(f"fig9_vr_{shards}shard", us / REQS,
                       f"proj={proj_rps/1e3:.0f}kOps cpu={REQS/(us/1e6):.0f}rps"))
    lat = chain_latency_ns([(0, 0), (1, 0), (2, 0), (3, 0), (2, 1), (1, 1),
                            (0, 1)], payload_bytes=16)
    out.append(row("table3_vr_latency_floor", lat / 1000,
                   f"noc_chain={lat:.0f}ns"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
