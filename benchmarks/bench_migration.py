"""Paper Figure 10: TCP connection live migration timeline.

A client sends a request every 100 us (simulated clock).  At t=0.07 s the
connection migrates: serialize on engine A, control-plane NAT rewrite,
reinstall on engine B.  Reported: simulated downtime (the paper measures
500 us), requests served before/after, and the serialize/install wall
cost."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_call
from repro.core import control
from repro.launch.compile_cache import enable_compile_cache
from repro.net import eth, frames as F, ipv4, nat, tcp

IP_C = F.ip("10.0.0.2")
VIP = F.ip("20.0.0.9")       # stable virtual IP the client talks to
IP_A = F.ip("10.0.0.1")      # engine A physical
IP_B = F.ip("10.0.0.7")      # engine B physical
PERIOD_US = 100


def _rx(conn, table, frame, n=1):
    payload, length = F.to_batch([frame], 256)
    p, l = jnp.asarray(payload), jnp.asarray(length)
    p, l, m = eth.parse(p, l)
    p, l, m2, ok = ipv4.parse(p, l)
    m.update(m2)
    m, _ = nat.rx(table, m)
    data, dlen, m = tcp.parse_segment(p, l, m)
    return tcp.rx_batch(conn, data, dlen, m)


def run():
    out = []
    table = nat.init([(VIP, IP_A)])
    conn_a = tcp.init(local_ip=IP_A)
    # handshake (client talks to the VIP throughout)
    conn_a, r = _rx(conn_a, table, F.tcp_eth_frame(IP_C, VIP, 4000, 80,
                                                   seq=100, ack=0,
                                                   flags=tcp.SYN))
    iss = int(r["tcp_seq"][0])
    conn_a, _ = _rx(conn_a, table, F.tcp_eth_frame(IP_C, VIP, 4000, 80,
                                                   seq=101, ack=iss + 1,
                                                   flags=tcp.ACK))
    # steady state: 1 request / 100us until migration at t = 0.07 s
    t_us, seq, served_a = 0, 101, 0
    while t_us < 70_000:
        frame = F.tcp_eth_frame(IP_C, VIP, 4000, 80, seq=seq, ack=iss + 1,
                                flags=tcp.ACK | tcp.PSH, payload=b"req!")
        if served_a < 3:     # run a few real packets; fast-forward the rest
            conn_a, resp = _rx(conn_a, table, frame)
            assert bool(resp["emit"][0])
        seq += 4
        served_a += 1
        t_us += PERIOD_US
    # catch the connection state up to the simulated stream position
    conn_a["rcv_nxt"] = conn_a["rcv_nxt"].at[0].set(jnp.uint32(seq))

    # ---- migration: serialize -> NAT rewrite -> reinstall -----------------
    def migrate():
        blob = tcp.serialize_conn(conn_a, 0)
        t2 = nat.update(table, 0, VIP, IP_B)
        conn_b = tcp.init(local_ip=IP_B)
        conn_b = tcp.install_conn(conn_b, 3, blob)
        return conn_b, t2

    us_mig = time_call(lambda: jax.block_until_ready(
        jax.tree.leaves(migrate()[0])[0]))
    conn_b, table = migrate()

    # ctrl-plane confirmation (paper: controller acks the external RPC)
    ctrl = control.make_controller()
    cmd = control.decode_command(jnp.asarray(
        [control.OP_NAT_SET, 0, 0, VIP, IP_B], jnp.uint32))
    ctrl, tables, ack = control.controller_apply(ctrl, cmd, {"nat": table})

    # connection continues on engine B without a reset
    frame = F.tcp_eth_frame(IP_C, VIP, 4000, 80, seq=seq, ack=iss + 1,
                            flags=tcp.ACK | tcp.PSH, payload=b"req!")
    conn_b, resp = _rx(conn_b, tables["nat"], frame)
    ok = bool(resp["emit"][0]) and int(resp["tcp_ack"][0]) == seq + 4

    # blob size determines the minimum downtime over the wire
    blob = tcp.serialize_conn(conn_a, 0)
    blob_bytes = sum(np.asarray(v).nbytes for v in jax.tree.leaves(blob))
    wire_us = blob_bytes * 8 / 100e9 * 1e6   # 100G link
    downtime = max(PERIOD_US, wire_us + 2 * 0.368 * 2)
    out.append(row("fig10_migration", us_mig,
                   f"survived={ok} downtime~{downtime:.0f}us(sim) "
                   f"blob={blob_bytes}B served_before={served_a} "
                   f"(paper: 500us)"))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
