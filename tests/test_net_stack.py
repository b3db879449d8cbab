"""Network stack: golden-frame interop (Linux wire format), checksums,
TCP engine behaviour, NAT, RPC framing."""
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.net import bytesops as B
from repro.net import eth, frames as F, ipinip, ipv4, nat, rpc, tcp, udp

IP_A = F.ip("10.0.0.2")     # client
IP_S = F.ip("10.0.0.1")     # server/accelerator


def rx_udp(frames_list, max_len=512):
    payload, length = F.to_batch(frames_list, max_len)
    p, l = jnp.asarray(payload), jnp.asarray(length)
    p, l, m = eth.parse(p, l)
    p, l, m2, ok_ip = ipv4.parse(p, l)
    m.update(m2)
    p, l, m3, ok_udp = udp.parse(p, l, m)
    return p, l, m3, ok_ip & ok_udp


# ---------------------------------------------------------------------------
# UDP path


def test_udp_rx_parses_golden_frame():
    fr = F.udp_rpc_frame(IP_A, IP_S, 5555, 9000, b"hello")
    p, l, m, ok = rx_udp([fr])
    assert bool(ok[0])
    assert int(m["src_port"][0]) == 5555 and int(m["dst_port"][0]) == 9000
    assert bytes(p[0, :l[0]].tolist()) == b"hello"


def test_udp_vlan_tagged():
    fr = F.udp_rpc_frame(IP_A, IP_S, 5555, 9000, b"v", vlan=7)
    p, l, m, ok = rx_udp([fr])
    assert bool(ok[0]) and int(l[0]) == 1


def _np_strip(rows, n):
    """Each row without its first n[i] bytes, zero-filled at the tail."""
    out = np.zeros_like(rows)
    for i, k in enumerate(n):
        out[i, :rows.shape[1] - k] = rows[i, k:]
    return out


def _ipv4_with_options(rng, words, payload, bad_csum):
    """An IPv4 header of ``words`` 32-bit words (options random bytes)."""
    opts = rng.integers(0, 256, 4 * (words - 5), dtype=np.uint8).tobytes()
    hdr = struct.pack("!BBHHHBBH", 0x40 | words, 0, 4 * words + len(payload),
                      0, 0x4000, 64, 17, 0) + struct.pack("!II", IP_A, IP_S)
    hdr += opts
    csum = B.np_checksum16(hdr) ^ int(bad_csum)
    return hdr[:10] + struct.pack("!H", csum) + hdr[12:] + payload


def test_vlan_and_ip_options_strip_like_numpy():
    """eth.parse over frames with and without a VLAN tag, then
    ipv4.parse_ex over IHL 5-15: each strip, length and verdict equals a
    numpy strip of the same frames."""
    rng = np.random.default_rng(7)
    vlan = rng.random(64) < 0.5
    words = np.arange(64) % 11 + 5
    bad = rng.random(64) < 0.2
    frames = [F.eth_frame(b"\x02" * 6, b"\x04" * 6, eth.ETHERTYPE_IPV4,
                          _ipv4_with_options(rng, int(w), bytes(
                              rng.integers(0, 256, 1 + i * 7, np.uint8)), b),
                          vlan=7 if v else None)
              for i, (v, w, b) in enumerate(zip(vlan, words, bad))]
    rows, length = F.to_batch(frames, 640)
    p, l, meta = eth.parse(jnp.asarray(rows), jnp.asarray(length))
    hlen = np.where(vlan, eth.VLAN_HLEN, eth.ETH_HLEN)
    ip_rows = _np_strip(rows, hlen)
    np.testing.assert_array_equal(np.asarray(p), ip_rows)
    np.testing.assert_array_equal(np.asarray(l), length - hlen)
    np.testing.assert_array_equal(np.asarray(meta["vlan_tci"]),
                                  np.where(vlan, 7, 0))
    q, ql, _, ok, _ = ipv4.parse_ex(p, l)
    total = (ip_rows[:, 2].astype(np.int32) << 8) | ip_rows[:, 3]
    np.testing.assert_array_equal(np.asarray(q), _np_strip(ip_rows, 4 * words))
    np.testing.assert_array_equal(np.asarray(ql), total - 4 * words)
    np.testing.assert_array_equal(np.asarray(ok), ~bad)


def test_tcp_data_offset_strip_like_numpy():
    """tcp.parse_segment over data offsets 5-15 (options random bytes):
    the data and its length equal a numpy strip of the same segments."""
    rng = np.random.default_rng(8)
    doff = np.arange(33) % 11 + 5
    segs = [struct.pack("!HHIIBBHHH", 5555, 80, 1000 + i, 0, int(d) << 4,
                        F.TCP_ACK, 65535, 0, 0)
            + rng.integers(0, 256, 4 * (d - 5) + 3 * i, np.uint8).tobytes()
            for i, d in enumerate(doff)]
    rows, length = F.to_batch(segs, 192)
    data, dlen, meta = tcp.parse_segment(jnp.asarray(rows),
                                         jnp.asarray(length), {})
    np.testing.assert_array_equal(np.asarray(data), _np_strip(rows, 4 * doff))
    np.testing.assert_array_equal(np.asarray(dlen), length - 4 * doff)
    np.testing.assert_array_equal(np.asarray(meta["tcp_seq"]),
                                  1000 + np.arange(33))


def test_corrupted_ip_checksum_dropped():
    fr = bytearray(F.udp_rpc_frame(IP_A, IP_S, 5555, 9000, b"x"))
    fr[20] ^= 0xFF          # corrupt an IP header byte
    p, l, m, ok = rx_udp([bytes(fr)])
    assert not bool(ok[0])


def test_udp_tx_roundtrip_checksum_valid():
    fr = F.udp_rpc_frame(IP_A, IP_S, 5555, 9000, b"ping!")
    p, l, m, ok = rx_udp([fr])
    # build reply (swap all fields)
    m_tx = dict(m)
    m_tx["src_ip"], m_tx["dst_ip"] = m["dst_ip"], m["src_ip"]
    m_tx["src_port"], m_tx["dst_port"] = m["dst_port"], m["src_port"]
    m_tx["ip_proto"] = jnp.full_like(m["src_ip"], 17)
    q, ql = udp.build(p, l, m_tx)
    q, ql = ipv4.build(q, ql, m_tx)
    m_tx["eth_dst_hi"], m_tx["eth_dst_lo"] = m["eth_src_hi"], m["eth_src_lo"]
    m_tx["eth_src_hi"], m_tx["eth_src_lo"] = m["eth_dst_hi"], m["eth_dst_lo"]
    m_tx["ethertype"] = m["ethertype"]
    q, ql = eth.build(q, ql, m_tx)
    # a Linux client would now parse this: verify via our own parser
    q2, l2, m2 = eth.parse(q, ql)
    q3, l3, m3, ok_ip = ipv4.parse(q2, l2)
    m2.update(m3)
    q4, l4, m4, ok_udp = udp.parse(q3, l3, m2)
    assert bool(ok_ip[0]) and bool(ok_udp[0])
    assert bytes(q4[0, :l4[0]].tolist()) == b"ping!"


def test_checksum_against_numpy_oracle():
    rng = np.random.default_rng(0)
    for n in (1, 2, 19, 64, 333):
        data = rng.integers(0, 256, (2, 512), dtype=np.uint8)
        got = B.checksum16(jnp.asarray(data), 0,
                           jnp.asarray([n, n], jnp.int32))
        want = B.np_checksum16(bytes(data[0, :n].tobytes()))
        assert int(got[0]) == want


# ---------------------------------------------------------------------------
# TCP engine


def tcp_rx_frames(conn, frames_list, max_len=600):
    payload, length = F.to_batch(frames_list, max_len)
    p, l = jnp.asarray(payload), jnp.asarray(length)
    p, l, m = eth.parse(p, l)
    p, l, m2, ok = ipv4.parse(p, l)
    m.update(m2)
    data, dlen, m = tcp.parse_segment(p, l, m)
    return tcp.rx_batch(conn, data, dlen, m)


def test_tcp_handshake_and_data():
    conn = tcp.init(local_ip=IP_S)
    syn = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=1000, ack=0,
                          flags=tcp.SYN)
    conn, resps = tcp_rx_frames(conn, [syn])
    assert bool(resps["emit"][0])
    assert int(resps["tcp_flags"][0]) == tcp.SYN | tcp.ACK
    assert int(resps["tcp_ack"][0]) == 1001
    iss = int(resps["tcp_seq"][0])

    ack = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=1001, ack=iss + 1,
                          flags=tcp.ACK)
    data = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=1001, ack=iss + 1,
                           flags=tcp.ACK | tcp.PSH, payload=b"GET /stats")
    conn, resps = tcp_rx_frames(conn, [ack, data])
    assert int(conn["accepts"]) == 1
    assert int(conn["state"][0]) == tcp.ESTABLISHED
    # data ACKed
    assert bool(resps["emit"][1])
    assert int(resps["tcp_ack"][1]) == 1001 + len(b"GET /stats")
    # app can read it (request/notify interface)
    assert bool(tcp.app_readable(conn, 0, 10))
    conn, rdata, ok = tcp.app_read(conn, 0, 10)
    assert bool(ok) and bytes(rdata.tolist()) == b"GET /stats"


def _establish(conn, sport=4000, seq0=5000):
    syn = F.tcp_eth_frame(IP_A, IP_S, sport, 80, seq=seq0, ack=0,
                          flags=tcp.SYN)
    conn, r = tcp_rx_frames(conn, [syn])
    iss = int(r["tcp_seq"][0])
    ack = F.tcp_eth_frame(IP_A, IP_S, sport, 80, seq=seq0 + 1, ack=iss + 1,
                          flags=tcp.ACK)
    conn, _ = tcp_rx_frames(conn, [ack])
    return conn, iss


def test_tcp_tx_and_fast_retransmit():
    conn = tcp.init(local_ip=IP_S)
    conn, iss = _establish(conn)
    conn, ok = tcp.app_send(conn, 0, jnp.asarray(list(b"response-bytes"),
                                                 jnp.uint8), 14)
    assert bool(ok)
    conn, seg, data, dlen = tcp.tx_emit(conn, 0, mss=8)
    assert bool(seg["emit"]) and int(dlen) == 8
    assert bytes(data[:8].tolist()) == b"response"
    assert int(seg["tcp_seq"]) == (iss + 1) & 0xFFFFFFFF
    conn, seg2, data2, dlen2 = tcp.tx_emit(conn, 0, mss=8)
    assert int(dlen2) == 6 and bytes(data2[:6].tolist()) == b"-bytes"

    # 3 duplicate ACKs at snd_una -> fast retransmit
    dup = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=5001, ack=iss + 1,
                          flags=tcp.ACK)
    conn, resps = tcp_rx_frames(conn, [dup, dup, dup])
    assert bool(resps["fast_retx"][2])
    conn, seg3, data3, dlen3 = tcp.tx_emit(conn, 0, mss=8, retransmit=True)
    assert int(seg3["tcp_seq"]) == (iss + 1) & 0xFFFFFFFF  # resend from una
    assert bytes(data3[:8].tolist()) == b"response"


def test_tcp_flow_control_window():
    conn = tcp.init(local_ip=IP_S)
    conn, iss = _establish(conn)
    # peer advertises a 4-byte window
    wnd = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=5001, ack=iss + 1,
                          flags=tcp.ACK, window=4)
    conn, _ = tcp_rx_frames(conn, [wnd])
    conn, ok = tcp.app_send(conn, 0,
                            jnp.asarray(list(b"0123456789"), jnp.uint8), 10)
    conn, seg, data, dlen = tcp.tx_emit(conn, 0, mss=8)
    assert int(dlen) == 4          # window-limited
    conn, seg2, data2, dlen2 = tcp.tx_emit(conn, 0, mss=8)
    assert int(dlen2) == 0         # window exhausted until ACK


def test_tcp_out_of_order_dropped_and_dup_acked():
    conn = tcp.init(local_ip=IP_S)
    conn, iss = _establish(conn)
    ooo = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=5010, ack=iss + 1,
                          flags=tcp.ACK | tcp.PSH, payload=b"late")
    conn, resps = tcp_rx_frames(conn, [ooo])
    assert bool(resps["emit"][0])
    assert int(resps["tcp_ack"][0]) == 5001      # dup ack at rcv_nxt
    assert not bool(tcp.app_readable(conn, 0, 1))


def test_tcp_timer_retransmit():
    conn = tcp.init(local_ip=IP_S)
    conn, iss = _establish(conn)
    conn, _ = tcp.app_send(conn, 0, jnp.asarray(list(b"abcd"), jnp.uint8), 4)
    conn, seg, _, _ = tcp.tx_emit(conn, 0, mss=8)
    assert int(conn["snd_nxt"][0]) == (iss + 5) & 0xFFFFFFFF
    for _ in range(8):
        conn, expired = tcp.tick(conn, timeout=8)
    assert bool(expired[0])
    assert int(conn["snd_nxt"][0]) == (iss + 1) & 0xFFFFFFFF  # go-back-N


def test_tcp_migration_serialize_reinstall():
    conn_a = tcp.init(local_ip=IP_S)
    conn_a, iss = _establish(conn_a)
    data = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=5001, ack=iss + 1,
                           flags=tcp.ACK | tcp.PSH, payload=b"state!")
    conn_a, _ = tcp_rx_frames(conn_a, [data])
    blob = tcp.serialize_conn(conn_a, 0)
    # reinstall on a different engine (the migration target)
    conn_b = tcp.init(local_ip=IP_S)
    conn_b = tcp.install_conn(conn_b, 3, blob)
    assert int(conn_b["state"][3]) == tcp.ESTABLISHED
    # connection continues: next in-order segment is accepted seamlessly
    more = F.tcp_eth_frame(IP_A, IP_S, 4000, 80, seq=5007, ack=iss + 1,
                           flags=tcp.ACK | tcp.PSH, payload=b"more")
    conn_b, resps = tcp_rx_frames(conn_b, [more])
    assert int(resps["tcp_ack"][0]) == 5011
    conn_b, rdata, ok = tcp.app_read(conn_b, 3, 10)
    assert bool(ok) and bytes(rdata.tolist()) == b"state!more"


# ---------------------------------------------------------------------------
# NAT + IPinIP + RPC


def test_nat_rx_tx_translation():
    table = nat.init([(F.ip("20.0.0.9"), IP_S)])   # virtual -> physical
    meta = {"dst_ip": jnp.asarray([F.ip("20.0.0.9")], jnp.uint32),
            "src_ip": jnp.asarray([IP_S], jnp.uint32)}
    m2, found = nat.rx(table, meta)
    assert bool(found[0]) and int(m2["dst_ip"][0]) == IP_S
    m3, found2 = nat.tx(table, meta)
    assert bool(found2[0]) and int(m3["src_ip"][0]) == F.ip("20.0.0.9")


def test_nat_control_plane_migration_rewrite():
    table = nat.init([(F.ip("20.0.0.9"), IP_S)])
    table = nat.update(table, 0, F.ip("20.0.0.9"), F.ip("10.0.0.7"))
    meta = {"dst_ip": jnp.asarray([F.ip("20.0.0.9")], jnp.uint32)}
    m2, found = nat.rx(table, meta)
    assert int(m2["dst_ip"][0]) == F.ip("10.0.0.7")


def test_ipinip_encap_roundtrip():
    inner = F.ipv4_packet(IP_A, IP_S, 17, b"payload")
    p, l = F.to_batch([inner], 256)
    p, l = jnp.asarray(p), jnp.asarray(l)
    meta = {"src_ip": jnp.asarray([IP_A], jnp.uint32),
            "dst_ip": jnp.asarray([IP_S], jnp.uint32)}
    q, ql = ipinip.encap(p, l, meta, F.ip("1.1.1.1"), F.ip("2.2.2.2"))
    # outer parse
    q2, l2, m2, ok = ipv4.parse(q, ql)
    assert bool(ok[0]) and int(m2["ip_proto"][0]) == ipinip.PROTO_IPIP
    inner2, il, ok2 = ipinip.decap(q2, l2, m2)
    # inner parses as the original packet
    q3, l3, m3, ok3 = ipv4.parse(inner2, il)
    assert bool(ok3[0]) and int(m3["src_ip"][0]) == IP_A


def test_rpc_frame_roundtrip():
    fr = rpc.np_frame(rpc.MSG_ECHO, 77, b"abc")
    p, l = F.to_batch([fr], 64)
    body, blen, meta, ok = rpc.parse(jnp.asarray(p), jnp.asarray(l))
    assert bool(ok[0]) and int(meta["req_id"][0]) == 77
    assert bytes(body[0, :blen[0]].tolist()) == b"abc"
    out, olen = rpc.build(body, blen, rpc.MSG_ECHO,
                          meta["req_id"])
    body2, blen2, meta2, ok2 = rpc.parse(out, olen)
    assert bool(ok2[0]) and bytes(body2[0, :blen2[0]].tolist()) == b"abc"
