"""UDP echo over four chips: the RSS-replicated echo stack
(``replicated_udp_topology(n_rx=2, policy="flow_hash")``) run as a
``ShardedStream``, flows split across shards by ``fill_rss``.

The reference is the echo's (``udp_echo.py``): a reply carries the
request's body unchanged.
"""
from __future__ import annotations

from bench import wire
from bench.configs.udp_echo import (expected, reply_cap, request_body_len,
                                    requests, served)
from bench.system import Sharded

__all__ = ["build", "expected", "reply_cap", "request_body_len", "requests",
           "served"]


def build(cfg):
    from repro.apps import echo
    from repro.net.stack import UdpStack, replicated_udp_topology
    apps = [echo.make(port=cfg["server_port"])]
    topo = replicated_udp_topology(apps, n_rx=cfg["n_rx"],
                                   policy=cfg["rx_policy"])
    stack = UdpStack(apps, wire.ip4(cfg["server_ip"]), topo=topo,
                     mgmt_port=cfg["mgmt_port"],
                     with_telemetry=cfg["telemetry"],
                     with_obs=cfg["observability"])
    return Sharded(stack, cfg["app"], cfg["shards"], cfg["n_batches"],
                   cfg["batch"], cfg["width"])
