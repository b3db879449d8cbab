"""UDP echo on one chip: ``UdpStack([echo.make(port)], mgmt_port=...)``.

The plain reference beside it: an echo reply carries the request's body
unchanged, so the reply body is the request body.  Requests and replies
are UDP/RPC frames (``bench/wire.py``).
"""
from __future__ import annotations

import numpy as np

from bench import wire
from bench.system import Single


def build(cfg):
    from repro.apps import echo
    from repro.net.stack import UdpStack
    stack = UdpStack([echo.make(port=cfg["server_port"])],
                     wire.ip4(cfg["server_ip"]), mgmt_port=cfg["mgmt_port"],
                     with_telemetry=cfg["telemetry"],
                     with_obs=cfg["observability"])
    return Single(stack, cfg["app"], cfg["n_batches"], cfg["batch"],
                  cfg["width"])


def request_body_len(cfg, frame_sizes):
    """The RPC body of a request frame of each size."""
    return np.asarray(frame_sizes) - wire.HDR


def requests(cfg, pool):
    return wire.requests(cfg, pool)


def reply_cap(cfg, pool) -> int:
    """The longest reply a request of the pool can get."""
    return wire.HDR + int(np.max(pool.body_len))


def expected(cfg, pool, cap):
    """Reference: each request's body, echoed back to its client."""
    return wire.replies(cfg, pool, pool.body, pool.body_len, cap)


def served(state) -> int:
    """The echo tile's count of replies, from one shard's state."""
    return int(np.sum(state["apps"]["echo"]["served"]))
