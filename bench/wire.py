"""Wire formats of the benchmark's client, built with numpy alone.

The UDP/RPC configurations' requests and the replies they expect are
Ethernet II + IPv4 + UDP + RPC frames, built here in bulk: one (R, width)
uint8 array for R frames, checksums computed over whole columns at once.
``requests`` and ``replies`` frame a pool this way; a configuration over
another protocol frames its pool in its own module.  This module is part
of the yardstick and imports nothing of the system under test.

RPC framing (big-endian): magic u16 0xBEE5, msg_type u8, req_id u32,
payload_len u16, payload.
"""
from __future__ import annotations

import numpy as np

from bench import traffic

ETH, IPV4, UDP, RPC = 14, 20, 8, 9
HDR = ETH + IPV4 + UDP + RPC           # 51 bytes in front of an RPC body
RPC_MAGIC = 0xBEE5
IP_IDENT = 18                          # first byte of the IPv4 ident field
UDP_CSUM = ETH + IPV4 + 6              # first byte of the UDP checksum
UNBOUND_PORT = 9                       # discard: nothing listens there


def ip4(text: str) -> int:
    a, b, c, d = (int(x) for x in text.split("."))
    return (a << 24) | (b << 16) | (c << 8) | d


def mac(text: str) -> np.ndarray:
    return np.array([int(x, 16) for x in text.split(":")], np.uint8)


def _put(buf, col, values, nbytes):
    """Write ``values`` (R,) big-endian into ``nbytes`` columns at ``col``."""
    v = np.asarray(values, np.uint64)
    for i in range(nbytes):
        shift = np.uint64(8 * (nbytes - 1 - i))
        buf[:, col + i] = ((v >> shift) & np.uint64(0xFF)).astype(np.uint8)


def _fold(total):
    """Ones'-complement fold of (R,) uint64 sums to 16 bits."""
    total = np.asarray(total, np.uint64)
    while np.any(total >> np.uint64(16)):
        total = (total & np.uint64(0xFFFF)) + (total >> np.uint64(16))
    return total


def _sum16(buf, start, stop):
    """Sum of the big-endian 16-bit words in columns [start, stop) of each
    row; ``stop - start`` is even, and bytes past a row's end are zero."""
    cols = buf[:, start:stop].astype(np.uint64)
    return (cols[:, 0::2] << np.uint64(8)).sum(1) + cols[:, 1::2].sum(1)


def build(src_ip, dst_ip, src_port, dst_port, msg_type, req_id, body,
          body_len, src_mac, dst_mac, width: int):
    """R frames of Ethernet + IPv4 + UDP + RPC around ``body`` (R, Lb)
    uint8, of which the first ``body_len`` (R,) bytes of each row count.
    Every per-frame field is a scalar or an (R,) array.  Returns
    ``(frames (R, width) uint8, lengths (R,) int32)``; bytes past each
    frame's end are zero.  Header fields follow the reference stack:
    IPv4 ident 0, DF set, TTL 64; a UDP checksum that computes to 0 is
    sent as 0xFFFF."""
    body_len = np.asarray(body_len, np.int64)
    n = body_len.shape[0]
    flen = HDR + body_len
    if n and int(flen.max()) > width:
        raise ValueError(f"a {int(flen.max())} B frame does not fit "
                         f"{width} B rows")
    buf = np.zeros((n, width + (width & 1)), np.uint8)
    if body.shape[1]:
        cols = np.arange(body.shape[1])
        buf[:, HDR:HDR + body.shape[1]] = np.where(
            cols[None, :] < body_len[:, None], body, 0)
    full = lambda v: np.broadcast_to(np.asarray(v, np.int64), (n,))
    buf[:, 0:6] = dst_mac
    buf[:, 6:12] = src_mac
    _put(buf, 12, full(0x0800), 2)
    ip_len = IPV4 + UDP + RPC + body_len
    _put(buf, 14, full(0x4500), 2)
    _put(buf, 16, ip_len, 2)
    _put(buf, 18, full(0), 2)
    _put(buf, 20, full(0x4000), 2)
    _put(buf, 22, full((64 << 8) | 17), 2)
    _put(buf, 26, full(src_ip), 4)
    _put(buf, 30, full(dst_ip), 4)
    _put(buf, 24, 0xFFFF - _fold(_sum16(buf, 14, 34)), 2)
    udp_len = UDP + RPC + body_len
    _put(buf, 34, full(src_port), 2)
    _put(buf, 36, full(dst_port), 2)
    _put(buf, 38, udp_len, 2)
    _put(buf, 42, full(RPC_MAGIC), 2)
    _put(buf, 44, full(msg_type), 1)
    _put(buf, 45, full(req_id), 4)
    _put(buf, 49, body_len, 2)
    pseudo = (_sum16(buf, 26, 34) + np.uint64(17)
              + udp_len.astype(np.uint64))
    csum = 0xFFFF - _fold(pseudo + _sum16(buf, 34, buf.shape[1]))
    _put(buf, 40, np.where(csum == 0, 0xFFFF, csum), 2)
    return buf[:, :width], flen.astype(np.int32)


def requests(cfg: dict, pool) -> list:
    """The pool's requests as frames (bytes), client to ``cfg``'s server;
    an ``ip_csum`` request has its IPv4 header checksum broken, a
    ``no_route`` one goes to ``UNBOUND_PORT``."""
    blen = pool.body_len
    server_port = np.where(pool.kind == traffic.NO_ROUTE, UNBOUND_PORT,
                           int(cfg["server_port"]))
    buf, flen = build(
        pool.client_ip, ip4(cfg["server_ip"]), pool.client_port,
        server_port, int(cfg["msg_type"]), pool.req_id, pool.body, blen,
        mac(cfg["client_mac"]), mac(cfg["server_mac"]),
        HDR + int(blen.max()))
    buf[pool.kind == traffic.IP_CSUM, IP_IDENT] ^= 0x5A
    return [buf[i, :flen[i]].tobytes() for i in range(len(flen))]


def replies(cfg: dict, pool, body, body_len, cap: int):
    """The replies the server owes: ``body`` / ``body_len`` (R, ...) per
    request, framed back to each client.  Returns ``(frames (R, cap),
    frame lengths (R,), body lengths (R,))``, both lengths 0 where no
    reply is due."""
    ok = pool.kind == traffic.OK
    rlen = np.where(ok, body_len, 0)
    exp, exp_len = build(
        ip4(cfg["server_ip"]), pool.client_ip, cfg["server_port"],
        pool.client_port, cfg["msg_type"], pool.req_id, body, rlen,
        mac(cfg["server_mac"]), mac(cfg["client_mac"]), cap)
    return exp, np.where(ok, exp_len, 0), rlen
