"""Paper Table 4: per-tile resource utilization analog.

The FPGA metric (LUTs/BRAM) becomes compiled-HLO footprint per tile:
instruction count, per-call FLOPs, and HBM bytes for each protocol tile at
a fixed batch — the 'area' each tile occupies in the compiled program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro.launch import hlo_walk
from repro.launch.compile_cache import enable_compile_cache
from repro.net import eth, frames as F, ipv4, tcp, udp

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
BATCH = 64


def _walk(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    w = hlo_walk.walk(text)
    n_instr = sum(text.count(op) for op in (" fusion(", " dot(",
                                            " dynamic-slice("))
    return w, n_instr


def run():
    out = []
    fr = F.udp_rpc_frame(IP_C, IP_S, 5000, 7, b"x" * 64)
    payload, length = F.to_batch([fr] * BATCH, 256)
    p, l = jnp.asarray(payload), jnp.asarray(length)

    tiles = {
        "eth_rx": lambda pp, ll: eth.parse(pp, ll),
        "ip_rx": lambda pp, ll: ipv4.parse(*eth.parse(pp, ll)[:2]),
        "udp_rx": lambda pp, ll: udp.parse(
            *(lambda a, b, m, ok: (a, b, m))(
                *ipv4.parse(*eth.parse(pp, ll)[:2])),),
    }
    for name, fn in tiles.items():
        w, n = _walk(fn, p, l)
        out.append(row(f"table4_{name}", 0,
                       f"instrs={n} bytes/pkt={w.hbm_bytes/BATCH:.0f} "
                       f"flops/pkt={w.flops/BATCH:.0f}"))

    # TCP RX engine (paper: 11672 LUTs vs 2984 for UDP RX processing)
    conn = tcp.init(local_ip=IP_S)
    frt = F.tcp_eth_frame(IP_C, IP_S, 4000, 80, seq=1, ack=0, flags=tcp.SYN)
    tp, tl = F.to_batch([frt] * 8, 256)

    def tcp_rx(c, pp, ll):
        a, b, m = eth.parse(pp, ll)
        a, b, m2, ok = ipv4.parse(a, b)
        m.update(m2)
        d, dl, m = tcp.parse_segment(a, b, m)
        return tcp.rx_batch(c, d, dl, m)
    w, n = _walk(tcp_rx, conn, jnp.asarray(tp), jnp.asarray(tl))
    out.append(row("table4_tcp_rx", 0,
                   f"instrs={n} bytes/pkt={w.hbm_bytes/8:.0f} "
                   f"flops/pkt={w.flops/8:.0f}"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
