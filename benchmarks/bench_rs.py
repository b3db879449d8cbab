"""Paper Table 2: Reed-Solomon scaling 1->4 replicas.

Measured: CPU throughput of the RS app behind the stack with n replicas
(linear scale-out = the paper's claim).  Derived: per-instance TPU
projection from the kernel's compiled traffic (paper: 15 Gbps/instance,
62 Gbps at 4) and bytes-moved-per-op (the energy proxy)."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (append_trajectory, hlo_traffic, row,
                               time_call)
from repro.apps import reed_solomon
from repro.kernels.rs_encode import ops as rs_ops
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import HBM_BW
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
REQS = 16
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_rs.json")


def run():
    out = []
    traj = {}
    rng = np.random.default_rng(0)
    # kernel-level projection (single instance)
    data = jnp.asarray(rng.integers(0, 256, (8, 65536), dtype=np.uint8))
    w = hlo_traffic(lambda d: rs_ops.rs_encode(d, use_pallas=False), data)
    in_bytes = 8 * 65536
    proj_gbps = HBM_BW / max(w.hbm_bytes, 1) * in_bytes * 8 / 1e9
    bytes_per_op = w.hbm_bytes / (in_bytes / 4096)   # per 4KiB request
    us_k = time_call(jax.jit(lambda d: rs_ops.rs_encode(d, use_pallas=False)),
                     data)
    out.append(row("table2_rs_kernel_1inst", us_k,
                   f"proj={proj_gbps:.1f}Gbps bytes/op={bytes_per_op:.0f}"))
    traj["kernel_us"] = us_k
    traj["kernel_proj_gbps"] = proj_gbps
    traj["kernel_bytes_per_op"] = bytes_per_op

    # stack-level linear scale-out, 1..4 replicas
    block = rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
    fr = F.udp_rpc_frame(IP_C, IP_S, 5000, 9000,
                         rpc.np_frame(rpc.MSG_RS_ENCODE, 0, block))
    payload, length = F.to_batch([fr] * REQS, 4400)
    p, l = jnp.asarray(payload), jnp.asarray(length)
    base_us = None
    for n in (1, 2, 3, 4):
        stack = UdpStack([reed_solomon.make(port=9000, n_replicas=n)], IP_S,
                         with_telemetry=False)
        state = stack.init_state()
        fn = jax.jit(lambda s, pp, ll: stack.rx_tx(s, pp, ll))
        us = time_call(fn, state, p, l)
        base_us = base_us or us
        speed = REQS * 4096 * 8 / (us / 1e6) / 1e9
        out.append(row(f"table2_rs_stack_{n}inst", us / REQS,
                       f"proj={proj_gbps * n:.1f}Gbps cpu={speed:.3f}Gbps "
                       f"scale={base_us / us * n:.2f}x"))
        traj[f"stack_{n}inst_us_per_req"] = us / REQS
        traj[f"stack_{n}inst_cpu_gbps"] = speed
    append_trajectory(OUT_PATH, traj)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
