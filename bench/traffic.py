"""The general traffic generator: reads a mix from
``bench/traffic/<mix>.json`` and makes the client's requests from
``--seed``.

A mix is data.  Its keys:

  loop          "closed": every window carries a full arena of requests;
                "open": requests arrive on their own schedule.
  flows         number of client flows; flow f is client 10.N.(f // 250).
                (f % 250 + 1) on UDP port ``base_port + f``, N being the
                mix's ``client_net`` octet.
  flow_zipf     optional: closed loop, a shard's frames pick its flows
                with Zipf weights 1 / rank^theta instead of uniformly.
  frame_sizes   frame lengths in bytes, drawn in the ratio of ``weights``
                (closed loop; a configuration with a fixed request body
                ignores them).
  bad           per shard and window, how many frames break on purpose:
                "ip_csum" (IPv4 header checksum broken) and "no_route"
                (sent to a port where nothing listens).
  rate_per_s    open loop: the offered load, in requests per second.
  arrivals      open loop, optional: "poisson" (the default), or
                {"on_off": [on_s, off_s]}: Poisson arrivals during the
                on periods only, at the rate that keeps ``rate_per_s``
                as the mean.
  pool_windows  how many distinct windows of requests the pool holds;
                the loop cycles through them.
  generator     optional: the name of a module ``bench/traffic/<name>.py``
                whose ``make_pool`` (same arguments as the one here) makes
                the requests instead, for traffic these keys cannot say.

Every seed gets the same multiset of frame sizes, bad frames and
inter-arrival gaps, in another order, so seeds change the order of the
work and not its amount.

The generator says what the client sends: which flow, how long a body,
which bytes, whether it is broken and when it is due.  The configuration
frames it (``requests``) and judges the replies: by default byte for byte
against replies it builds in advance (``expected``), or with a ``judge``
of its own where a reply can only be judged once it is seen, as a served
model's tokens are, by the reference's logits teacher-forced on them
(``harness.check`` gives the contract).  So a new protocol is a
configuration of its own.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Callable, List, Optional

import numpy as np

OK, IP_CSUM, NO_ROUTE = 0, 1, 2
KINDS = {"ip_csum": IP_CSUM, "no_route": NO_ROUTE}

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(HERE, "traffic")


def load_mix(name: str) -> dict:
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


def generator(mix: dict) -> Callable:
    """The ``make_pool`` that makes ``mix``'s requests: this module's, or
    the one of ``bench/traffic/<generator>.py`` where the mix names one."""
    name = mix.get("generator")
    if not name:
        return make_pool
    from bench.harness import load_file_module
    return load_file_module(os.path.join(MIXES, f"{name}.py"),
                            f"bench_traffic_{name}").make_pool


@dataclasses.dataclass
class Pool:
    """The client's requests, made once in set-up.

    ``kind`` says which must be answered (OK) and which must be dropped;
    ``body`` / ``body_len`` are what each request carries above its
    framing, ``frames`` the requests as bytes once the configuration has
    framed them.  Closed loop: ``windows`` (P, S, rows) lists the request
    in each arena row of each pool window, -1 for an empty row.  Open
    loop: ``due`` (n,) are the arrival offsets in seconds from the
    window's start, and arrival i is request ``i % len(kind)``."""
    loop: str
    shards: int
    rows: int
    kind: np.ndarray
    client_ip: np.ndarray
    client_port: np.ndarray
    req_id: np.ndarray
    body: np.ndarray
    body_len: np.ndarray
    windows: Optional[np.ndarray] = None
    due: Optional[np.ndarray] = None
    frames: Optional[List[bytes]] = None


def _counts(total: int, weights) -> np.ndarray:
    """Split ``total`` in the ratio of ``weights`` by largest remainder."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    n = np.floor(exact).astype(np.int64)
    n[np.argsort(-(exact - n), kind="stable")[:total - n.sum()]] += 1
    return n


def _gaps(n: int, rate: float) -> np.ndarray:
    """n inter-arrival gaps of a Poisson process at ``rate``: the
    exponential distribution's quantiles at (k + 0.5) / n."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def arrival_times(mix: dict, n: int, rng) -> np.ndarray:
    """n arrival offsets in seconds, as the mix's ``arrivals`` says."""
    rate = float(mix["rate_per_s"])
    process = mix.get("arrivals", "poisson")
    if process == "poisson":
        return np.cumsum(rng.permutation(_gaps(n, rate)))
    if isinstance(process, dict) and set(process) == {"on_off"}:
        on, off = (float(x) for x in process["on_off"])
        t_on = np.cumsum(rng.permutation(_gaps(n, rate * (on + off) / on)))
        return t_on + np.floor(t_on / on) * off
    raise ValueError(f"unknown arrivals {process!r}")


def _flow_addr(mix, flows):
    net = int(mix.get("client_net", 1))
    ip = (10 << 24) | (net << 16) | ((flows // 250) << 8) | (flows % 250 + 1)
    return ip.astype(np.int64), int(mix["base_port"]) + flows


def make_pool(mix: dict, cfg: dict, shards: int, rows: int, seed: int,
              seconds: float, body_len: Callable) -> Pool:
    """Requests for one run.  ``rows`` is the arena rows of one shard's
    window (n_batches x batch); ``body_len`` maps the mix's frame sizes
    to the body each request carries under the configuration's framing."""
    rng = np.random.default_rng(seed)
    loop = mix["loop"]
    P = int(mix.get("pool_windows", 4))
    n_flows = int(mix["flows"])
    sized = "frame_sizes" in mix and not cfg.get("request_body")
    if loop == "closed":
        per = rows                         # every row of every shard
        R = P * shards * per
        kind = np.zeros((P, shards, per), np.int8)
        sizes = np.zeros((P, shards, per), np.int64)
        flow = np.zeros((P, shards, per), np.int64)
        # fill_rss sends a flow to shard (client port % shards)
        ports = int(mix["base_port"]) + np.arange(n_flows)
        by_shard = [np.flatnonzero(ports % shards == s)
                    for s in range(shards)]
        theta = mix.get("flow_zipf")
        pick = [None if theta is None else
                1.0 / np.arange(1, len(f) + 1) ** float(theta)
                for f in by_shard]
        pick = [None if p is None else p / p.sum() for p in pick]
        bad = mix.get("bad", {})
        n_bad = sum(bad.values())
        if sized:
            base = np.repeat(np.asarray(mix["frame_sizes"], np.int64),
                             _counts(per, mix.get("weights", [1] * len(
                                 mix["frame_sizes"]))))
        for w in range(P):
            for s in range(shards):
                k = np.zeros(per, np.int8)
                k[:n_bad] = np.repeat([KINDS[b] for b in bad],
                                      [bad[b] for b in bad])
                kind[w, s] = rng.permutation(k)
                if sized:
                    sizes[w, s] = rng.permutation(base)
                flow[w, s] = rng.choice(by_shard[s], per, p=pick[s])
        if shards > 1:
            # RSS hands each shard its flows whole, one after another in
            # the order they first appear: the arena holds them so
            for w in range(P):
                for s in range(shards):
                    f = flow[w, s]
                    _, first = np.unique(f, return_index=True)
                    rank = np.empty(n_flows, np.int64)
                    rank[f[np.sort(first)]] = np.arange(len(first))
                    order = np.argsort(rank[f], kind="stable")
                    kind[w, s], sizes[w, s], flow[w, s] = (
                        kind[w, s][order], sizes[w, s][order], f[order])
        kind, sizes, flow = kind.ravel(), sizes.ravel(), flow.ravel()
        windows = np.arange(R).reshape(P, shards, per)
        due = None
    elif loop == "open":
        if shards != 1:
            raise ValueError("an open-loop mix runs on one shard")
        R = P * rows
        kind = np.zeros(R, np.int8)
        flow = np.arange(R) % n_flows
        sizes = np.full(R, int(mix.get("frame_sizes", [0])[0]), np.int64)
        n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
        due = arrival_times(mix, n, rng)
        windows = None
    else:
        raise ValueError(f"unknown loop {loop!r}")

    blen = np.asarray(body_len(sizes), np.int64)
    body = rng.integers(0, 256, (R, int(blen.max())), dtype=np.uint8)
    cip, cport = _flow_addr(mix, flow)
    return Pool(loop=loop, shards=shards, rows=rows, kind=kind,
                client_ip=cip, client_port=cport,
                req_id=np.arange(R, dtype=np.int64), body=body,
                body_len=blen, windows=windows, due=due)
