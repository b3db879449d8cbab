"""Streaming executor (device-resident multi-batch scan).

Acceptance coverage for the streamed path:
  * `run_stream` output — outputs AND state, telemetry counters included —
    is bit-identical to N sequential `run` calls over UDP, TCP, and the
    ipinip-tunneled topology;
  * zero host transfers inside the scanned region (jaxpr/HLO inspection);
  * runtime ROUTE_SET between stream chunks takes effect on the next
    chunk without recompilation;
  * compile-time dead-stage pruning drops statically unreachable stages
    (and never prunes port-keyed routes — the runtime-rewritable CAMs);
  * `FrameArena` fill-in-place semantics and the `to_batch` error fix.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import echo
from repro.core.compiler import StackCompiler
from repro.launch.certify import assert_no_host_callbacks
from repro.net import frames as F, ipinip, rpc, tcp
from repro.net.stack import (TcpStack, UdpStack, ipinip_udp_topology,
                             udp_topology)

IP_C = F.ip("10.0.0.2")
IP_S = F.ip("10.0.0.1")
TUN_C, TUN_S = F.ip("1.1.1.1"), F.ip("2.2.2.2")


def assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = {jax.tree_util.keystr(k): v
          for k, v in jax.tree_util.tree_leaves_with_path(b)}
    assert len(la) == len(lb)
    for k, v in la:
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(lb[jax.tree_util.keystr(k)]),
            err_msg=jax.tree_util.keystr(k))


def echo_frame(sport, req=1, port=7, payload=b"x", dst=IP_S):
    return F.udp_rpc_frame(IP_C, dst, sport, port,
                           rpc.np_frame(rpc.MSG_ECHO, req, payload))


def udp_arena(n_batches=3, batch=4, max_len=256):
    """Per-batch distinct traffic, including an unknown port and a corrupt
    frame so drops land in the telemetry counters."""
    arena = F.FrameArena(n_batches, batch, max_len)
    frames = []
    for i in range(n_batches * batch - 2):
        frames.append(echo_frame(5000 + i, req=i))
    frames.append(echo_frame(7000, req=98, port=4444))       # unknown port
    corrupt = bytearray(echo_frame(7001, req=99))
    corrupt[20] ^= 0xFF                                      # IP checksum
    frames.append(bytes(corrupt))
    arena.fill(frames)
    return arena


# ---------------------------------------------------------------------------
# bit-identity: streamed == N sequential batches (telemetry included)


def test_udp_run_stream_bit_identical():
    stack = UdpStack([echo.make(port=7, n_replicas=2)], IP_S)
    arena = udp_arena()
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    seq_state = stack.init_state()
    seq = {"tx_payload": [], "tx_len": [], "alive": []}
    for i in range(arena.n_batches):
        seq_state, q, ql, alive, info = stack.rx_tx(seq_state, p[i], l[i])
        seq["tx_payload"].append(q)
        seq["tx_len"].append(ql)
        seq["alive"].append(alive)

    st, outs = stack.run_stream(stack.init_state(), p, l)
    assert_trees_equal(st, seq_state)                 # telemetry included
    for k, rows in seq.items():
        np.testing.assert_array_equal(np.asarray(outs[k]),
                                      np.stack([np.asarray(r)
                                                for r in rows]))


def test_udp_run_stream_jit_matches_eager():
    stack = UdpStack([echo.make(port=7)], IP_S)
    arena = udp_arena(n_batches=2)
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)
    st_e, outs_e = stack.run_stream(stack.init_state(), p, l)
    st_j, outs_j = jax.jit(stack.run_stream)(stack.init_state(), p, l)
    assert_trees_equal(st_e, st_j)
    assert_trees_equal(outs_e, outs_j)


def test_ipinip_run_stream_bit_identical():
    apps = [echo.make(port=7)]
    stack = UdpStack(apps, IP_S, topo=ipinip_udp_topology(apps),
                     options={"outer_src": TUN_S, "outer_dst": TUN_C})

    def tunneled(sport, req):
        inner_udp = F.udp_datagram(IP_C, IP_S, sport, 7,
                                   rpc.np_frame(rpc.MSG_ECHO, req, b"tun"))
        inner_ip = F.ipv4_packet(IP_C, IP_S, 17, inner_udp)
        outer_ip = F.ipv4_packet(TUN_C, TUN_S, ipinip.PROTO_IPIP, inner_ip)
        return F.eth_frame(b"\x02\x00\x00\x00\x00\x01",
                           b"\x02\x00\x00\x00\x00\x02", 0x0800, outer_ip)

    arena = F.FrameArena(2, 2, 256)
    arena.fill([tunneled(5000, 1), echo_frame(5001, 2),   # plain one dies
                tunneled(5002, 3), tunneled(5003, 4)])
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    seq_state = stack.init_state()
    rows = []
    for i in range(arena.n_batches):
        seq_state, q, ql, alive, info = stack.rx_tx(seq_state, p[i], l[i])
        rows.append((q, ql, alive))
    st, outs = stack.run_stream(stack.init_state(), p, l)
    assert_trees_equal(st, seq_state)
    np.testing.assert_array_equal(
        np.asarray(outs["alive"]), np.stack([np.asarray(r[2])
                                             for r in rows]))
    np.testing.assert_array_equal(
        np.asarray(outs["tx_payload"]), np.stack([np.asarray(r[0])
                                                  for r in rows]))


def test_tcp_run_stream_bit_identical():
    """The scan carry really threads engine state: SYN -> ACK -> data
    across three streamed batches matches three sequential rx calls."""
    mk = lambda: TcpStack(IP_S, max_conns=4)
    ref, stk = mk(), mk()

    syn = F.tcp_eth_frame(IP_C, IP_S, 4000, 80, seq=900, ack=0,
                          flags=tcp.SYN)
    st_r = ref.init_state()
    p0, l0 = F.to_batch([syn], 128)
    st_r, r0 = ref.rx(st_r, jnp.asarray(p0), jnp.asarray(l0))
    iss = int(r0["tcp_seq"][0])

    batches = [
        [syn],
        [F.tcp_eth_frame(IP_C, IP_S, 4000, 80, seq=901, ack=iss + 1,
                         flags=tcp.ACK)],
        [F.tcp_eth_frame(IP_C, IP_S, 4000, 80, seq=901, ack=iss + 1,
                         flags=tcp.ACK | tcp.PSH, payload=b"hello")],
    ]
    arena = F.FrameArena(3, 1, 128)
    arena.fill([b[0] for b in batches])
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    seq_state = ref.init_state()
    seq_resps = []
    for i in range(3):
        seq_state, resps = ref.rx(seq_state, p[i], l[i])
        seq_resps.append(resps)
    st, outs = stk.run_stream(stk.init_state(), p, l)
    assert_trees_equal(st["conn"], seq_state["conn"])
    assert_trees_equal(st, seq_state)
    for k in seq_resps[0]:
        np.testing.assert_array_equal(
            np.asarray(outs["tcp_resps"][k]),
            np.stack([np.asarray(r[k]) for r in seq_resps]), err_msg=k)
    # the engine really advanced through the stream
    assert int(st["conn"]["rcv_nxt"][0]) == 901 + 5


# ---------------------------------------------------------------------------
# zero host syncs inside the scanned region (acceptance)


def test_run_stream_zero_host_transfers():
    stack = UdpStack([echo.make(port=7)], IP_S)
    arena = udp_arena(n_batches=2)
    state = stack.init_state()
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    fn = lambda st, pp, ll: stack.run_stream(st, pp, ll)
    prims = assert_no_host_callbacks(fn, state, p, l)
    assert "scan" in prims                 # the N batches are ONE loop

    hlo = jax.jit(fn).lower(state, p, l).compile().as_text()
    low = hlo.lower()
    assert "infeed" not in low and "outfeed" not in low
    assert "send-to-host" not in low and "recv-from-host" not in low
    assert "while" in low                  # scan lowered device-resident


# ---------------------------------------------------------------------------
# runtime route rewrites between stream chunks (satellite)


def test_route_set_between_stream_chunks_no_recompile():
    stack = UdpStack([echo.make(port=7)], IP_S)
    traces = []

    def counted(st, p, l):
        traces.append(1)
        return stack.run_stream(st, p, l)

    fn = jax.jit(counted)
    arena = F.FrameArena(2, 2, 256)
    arena.fill([echo_frame(5000 + i, req=i, port=7777) for i in range(4)])
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    state = stack.init_state()
    state, outs = fn(state, p, l)
    assert not bool(np.asarray(outs["info"]["echo"]).any())   # port unbound

    # live CAM rewrite between chunks: bind 7777 to the echo node
    tbl = state["routes"]["udp_rx:udp_port"]
    state = dict(state)
    state["routes"] = dict(state["routes"])
    state["routes"]["udp_rx:udp_port"] = tbl.set_entry(
        15, 7777, stack.pipeline.order.index("echo"))

    state, outs = fn(state, p, l)
    assert bool(np.asarray(outs["info"]["echo"]).all())
    assert len(traces) == 1          # same compiled program served both


# ---------------------------------------------------------------------------
# dead-stage pruning (compile-time)


def test_dead_stage_is_pruned_and_output_unchanged():
    """A tile whose only in-edge contradicts an upstream static-field
    commitment (ip_proto=6 below a udp-only path) is dropped before
    tracing; the surviving pipeline is bit-identical to the clean one."""
    apps = lambda: [echo.make(port=7)]
    topo = udp_topology(apps())
    topo.add_tile("phantom", "controller", 3, 1)
    topo.add_route("udp_rx", "ip_proto", 6, "phantom")

    stack = UdpStack(apps(), IP_S, topo=topo)
    plain = UdpStack(apps(), IP_S)
    assert stack.pipeline.pruned == ["phantom"]
    assert "phantom" not in stack.pipeline.order
    assert stack.pipeline.order == plain.pipeline.order
    # the dead edge's CAM never materializes either
    assert "udp_rx:ip_proto" not in stack.pipeline.table_entries

    p, l = F.to_batch([echo_frame(5000)], 256)
    p, l = jnp.asarray(p), jnp.asarray(l)
    st_a = stack.init_state()
    st_b = plain.init_state()
    st_a, qa, qla, alive_a, _ = stack.rx_tx(st_a, p, l)
    st_b, qb, qlb, alive_b, _ = plain.rx_tx(st_b, p, l)
    np.testing.assert_array_equal(np.asarray(qa), np.asarray(qb))
    np.testing.assert_array_equal(np.asarray(qla), np.asarray(qlb))
    assert_trees_equal(st_a, st_b)


def test_port_keyed_routes_are_never_pruned():
    """udp_port/tcp_port CAMs are the runtime-rewritable surface: a node
    reachable only through a port key stays compiled even if no traffic
    matches it yet (ROUTE_SET may bind it live)."""
    topo = udp_topology([echo.make(port=7)])
    topo.add_tile("parked", "controller", 3, 1)
    topo.add_route("udp_rx", "udp_port", 9999, "parked")
    stack = UdpStack([echo.make(port=7)], IP_S, topo=topo)
    assert stack.pipeline.pruned == []
    assert "parked" in stack.pipeline.order


def test_prune_exempts_fields_reparsed_by_duplicated_tiles():
    """The ipinip pattern duplicates ip_rx to re-parse the inner header
    (paper §3.5), making ip_proto runtime-dependent: a keyed route on the
    inner value LOOKS contradictory to the outer commitment (4 vs 17) but
    fires at runtime — pruning must leave the whole field alone."""
    apps = [echo.make(port=7)]
    topo = ipinip_udp_topology(apps)
    # key the inner hop on the re-parsed inner protocol instead of const
    for r in topo.tile("ip_rx_inner").routes:
        if r.next_tile == "udp_rx":
            r.match, r.key = "ip_proto", 17
    stack = UdpStack(apps, IP_S, topo=topo,
                     options={"outer_src": TUN_S, "outer_dst": TUN_C})
    assert stack.pipeline.pruned == []
    assert "udp_rx" in stack.pipeline.order

    inner_udp = F.udp_datagram(IP_C, IP_S, 5000, 7,
                               rpc.np_frame(rpc.MSG_ECHO, 1, b"inner"))
    inner_ip = F.ipv4_packet(IP_C, IP_S, 17, inner_udp)
    outer_ip = F.ipv4_packet(TUN_C, TUN_S, ipinip.PROTO_IPIP, inner_ip)
    frame = F.eth_frame(b"\x02\x00\x00\x00\x00\x01",
                        b"\x02\x00\x00\x00\x00\x02", 0x0800, outer_ip)
    p, l = F.to_batch([frame], 256)
    state, q, ql, alive, info = stack.rx_tx(
        stack.init_state(), jnp.asarray(p), jnp.asarray(l))
    assert bool(alive[0]) and bool(info["echo"][0])


def test_prune_keeps_multi_path_nodes():
    """A node with one dead and one feasible in-edge survives."""
    topo = udp_topology([echo.make(port=7)])
    topo.add_tile("dual", "controller", 3, 1)
    topo.add_route("udp_rx", "ip_proto", 6, "dual")      # dead edge
    topo.add_route("udp_rx", "const", None, "dual")      # feasible edge
    compiler = StackCompiler(topo, bindings={"echo": echo.make(port=7)},
                             options={"local_ip": IP_S})
    pipe = compiler.compile("eth_rx")
    assert pipe.pruned == [] and "dual" in pipe.order


# ---------------------------------------------------------------------------
# FrameArena + to_batch (satellite)


def test_frame_arena_fill_clears_stale_bytes():
    arena = F.FrameArena(2, 2, 64)
    used = arena.fill([b"\xAA" * 48, b"\xBB" * 10, b"\xCC" * 5])
    assert used == 2
    assert arena.length[0, 0] == 48 and arena.length[1, 1] == 0
    arena.fill([b"\xDD" * 4])                    # shorter refill
    assert arena.length[0, 0] == 4
    assert arena.payload[0, 0, 4:].max() == 0    # no stale 0xAA tail
    assert arena.payload[1].max() == 0


def test_frame_arena_errors_name_the_offender():
    arena = F.FrameArena(1, 2, 32)
    with pytest.raises(ValueError, match="frame 1 is 40 bytes"):
        arena.fill([b"x" * 8, b"y" * 40])
    with pytest.raises(ValueError, match="exceed the arena's capacity"):
        arena.fill([b"x"] * 3)


def test_to_batch_autosizes_and_raises_clearly():
    payload, length = F.to_batch([b"abc", b"defgh"])     # no max_len
    assert payload.shape == (2, 5)
    assert length.tolist() == [3, 5]
    with pytest.raises(ValueError, match="frame 1 is 5 bytes"):
        F.to_batch([b"abc", b"defgh"], max_len=4)
    assert F.to_batch([], )[0].shape == (0, 1)           # empty is fine


# ---------------------------------------------------------------------------
# perf regression smoke (slow lane)


@pytest.mark.slow
def test_streamed_pps_not_below_per_batch():
    """The streamed path must never regress below the per-batch harness
    pattern (fresh pack + transfer + dispatch + sync per batch) — the
    same measurement `make bench-stream` runs, smaller window, relaxed
    threshold (the quantitative >=3x gate lives in the bench)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.bench_stream import measure

    r = measure(n_batches=16, batch=8, repeats=3)
    assert r["speedup"] >= 1.0, (
        f"streamed {r['streamed_pps']:.0f}pps < per-batch "
        f"{r['per_batch_pps']:.0f}pps")
