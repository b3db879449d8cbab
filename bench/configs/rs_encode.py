"""RS(8,2) erasure-coding RPC on one chip:
``rpc_serve_topology([("rs", "rs_serve", MSG_RS_ENCODE)],
params={"rs": {"use_pallas": True}})``.

The plain reference beside it: RS(k, p) parity over GF(256), written
out from its definition.  A request's 4096 data bytes are k shards of
4096 / k bytes; parity shard j is the XOR over i of g[j][i] * shard i,
with g[j][i] = 2^((j + 1) i) and the field's polynomial ``gf_poly``.
Requests and replies are UDP/RPC frames (``bench/wire.py``).
"""
from __future__ import annotations

import numpy as np

from bench import wire
from bench.system import Single


def build(cfg):
    from repro.net import rpc
    from repro.net.stack import UdpStack, rpc_serve_topology
    if cfg["msg_type"] != rpc.MSG_RS_ENCODE:
        raise ValueError(f"msg_type {cfg['msg_type']} is not the program's "
                         f"MSG_RS_ENCODE ({rpc.MSG_RS_ENCODE})")
    topo = rpc_serve_topology(
        [(cfg["app"], "rs_serve", cfg["msg_type"])],
        params={cfg["app"]: {"use_pallas": cfg["use_pallas"]}})
    stack = UdpStack([], wire.ip4(cfg["server_ip"]), topo=topo,
                     mgmt_port=cfg["mgmt_port"],
                     with_telemetry=cfg["telemetry"],
                     with_obs=cfg["observability"])
    return Single(stack, cfg["app"], cfg["n_batches"], cfg["batch"],
                  cfg["width"])


def gf_mul_table(poly: int) -> np.ndarray:
    """(256, 256) products over GF(2^8) modulo ``poly``, by shift and
    add."""
    a = np.arange(256, dtype=np.int64)
    table = np.zeros((256, 256), np.int64)
    for b in range(256):
        x, y, acc = a.copy(), b, np.zeros(256, np.int64)
        while y:
            if y & 1:
                acc ^= x
            x = x << 1
            x = np.where(x & 0x100, x ^ poly, x)
            y >>= 1
        table[:, b] = acc
    return table.astype(np.uint8)


def gf_pow2(n: int, poly: int) -> int:
    x = 1
    for _ in range(n):
        x <<= 1
        if x & 0x100:
            x ^= poly
    return x


def parity(data: np.ndarray, k: int, p: int, poly: int) -> np.ndarray:
    """(R, k * S) data bytes -> (R, p * S) parity bytes."""
    R, total = data.shape
    S = total // k
    mul = gf_mul_table(poly)
    shards = data.reshape(R, k, S)
    out = np.zeros((R, p, S), np.uint8)
    for j in range(p):
        for i in range(k):
            out[:, j] ^= mul[shards[:, i], gf_pow2((j + 1) * i, poly)]
    return out.reshape(R, p * S)


def request_body_len(cfg, frame_sizes):
    """Every request carries ``request_body`` bytes of data."""
    return np.full(np.shape(frame_sizes), cfg["request_body"], np.int64)


def requests(cfg, pool):
    return wire.requests(cfg, pool)


def reply_cap(cfg, pool) -> int:
    return wire.HDR + cfg["request_body"] // cfg["rs_k"] * cfg["rs_p"]


def reply_body(cfg, body):
    """Reference: each request's parity shards."""
    data = body[:, :cfg["request_body"]]
    return parity(data, cfg["rs_k"], cfg["rs_p"], cfg["gf_poly"])


def expected(cfg, pool, cap):
    """Reference: each request's parity, sent back to its client."""
    rbody = reply_body(cfg, pool.body)
    return wire.replies(cfg, pool, rbody,
                        np.full(len(rbody), rbody.shape[1]), cap)


def served(state) -> int:
    """The rs tile's count of requests encoded, from one shard's state."""
    return int(np.sum(state["apps"]["rs"]["ops"]))
