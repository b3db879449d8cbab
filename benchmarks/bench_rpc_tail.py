"""RPC request-latency tail: direct-attached vs host-mediated LM serving.

The paper's headline claim is that terminating the network stack ON the
accelerator removes the host from the request path.  This benchmark
measures that end to end with the real wire format (eth/ip/udp/rpc):

  * **direct** — each MSG_LM_GENERATE frame is a one-frame `run_stream`
    window through the compiled serve stack: parse tiles -> `lm_serve`
    app tile (one on-device decode step against session KV state living
    in the scan carry) -> reply framed by the tx tiles, all one device
    program.  Latency = dispatch to reply-frame-ready.
  * **host-mediated** — the pre-tentpole baseline (exactly the
    examples/serve_rpc.py deployment): the device stack parses the frame,
    the host syncs the payload out, drives the ServeEngine through
    `LmServerApp.handle` (decode dispatch + host-side position updates +
    sync per step), and frames the reply on the CPU.

Reports p50/p99/p999 over N requests round-robined across sessions and
**appends** a trajectory entry to ``BENCH_rpc_tail.json`` (history is the
point — each PR adds a point, nothing is overwritten).

Gate (`make bench-rpc-tail` fails otherwise): direct p99 <= 0.5x the
host-mediated p99.  Also asserts the compiled direct path has zero host
callbacks/transfers in the scanned region (same jaxpr walk as
tests/test_stream.py).
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_trajectory, row
from repro.apps import lm_server
from repro.configs.serve_smoke import MAX_SEQ, MAX_SESSIONS, serve_config
from repro.launch.certify import assert_no_host_callbacks
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model
from repro.net import eth, frames as F, ipv4, rpc, udp
from repro.net.stack import UdpStack, rpc_serve_topology
from repro.serve.engine import ServeEngine

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
LM_PORT = 9400
OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_rpc_tail.json")

@jax.jit
def _parse_rx(payload, length):
    """The host-mediated server's device-side ingest (the parse half of
    the stack, as in examples/serve_rpc.py) — the host then syncs the
    body out to drive the engine."""
    p, l, m = eth.parse(payload, length)
    p, l, m2, ok1 = ipv4.parse(p, l)
    m.update(m2)
    p, l, m3, ok2 = udp.parse(p, l, m)
    body, blen, rmeta, ok3 = rpc.parse(p, l)
    return body, blen, ok1 & ok2 & ok3


def _request_frame(session: int, req_id: int, prompt=()) -> bytes:
    return F.udp_rpc_frame(
        IP_C, IP_S, 5000 + session, LM_PORT,
        rpc.np_frame(rpc.MSG_LM_GENERATE, req_id,
                     lm_server.encode_request(session, 1, list(prompt))))


def _percentiles(lat_us):
    p50, p99, p999 = np.percentile(lat_us, [50.0, 99.0, 99.9])
    return {"n": len(lat_us), "p50_us": float(p50), "p99_us": float(p99),
            "p999_us": float(p999), "mean_us": float(np.mean(lat_us))}


def _assert_no_host_sync(stack, state, p, l):
    """Zero host transfers inside the compiled serve program (the
    acceptance assertion from tests/test_stream.py, applied here so the
    bench itself certifies what it measures)."""
    assert_no_host_callbacks(
        lambda st, pp, ll: stack.run_stream(st, pp, ll), state, p, l)


def measure(n_requests: int = 160, n_sessions: int = 4, warmup: int = 8,
            prompt_len: int = 6):
    cfg = serve_config()
    params = model.init_params(cfg, jax.random.key(0))
    prompts = [np.arange(1 + s, 1 + s + prompt_len, dtype=np.int32)
               for s in range(n_sessions)]

    # ---- direct-attached path --------------------------------------------
    eng_d = ServeEngine(cfg, params, max_sessions=MAX_SESSIONS,
                        max_seq=MAX_SEQ)
    smap = {100 + s: eng_d.new_session(prompts[s])
            for s in range(n_sessions)}
    lm = lm_server.make_tile(cfg, params, max_sessions=MAX_SESSIONS,
                             max_seq=MAX_SEQ)
    stack = UdpStack([lm], IP_S,
                     topo=rpc_serve_topology(
                         [("lm", "lm_serve", rpc.MSG_LM_GENERATE)]))
    state = stack.init_state()
    state["apps"]["lm"] = lm_server.adopt_engine(state["apps"]["lm"],
                                                 eng_d, smap)

    frames = [_request_frame(100 + (i % n_sessions), i)
              for i in range(warmup + n_requests)]
    width = max(len(f) for f in frames) + 8
    # pre-staged device windows (the NIC's DMA ring), one frame each
    windows = []
    for f in frames:
        p, l = F.to_batch([f], width)
        windows.append((jnp.asarray(p)[None], jnp.asarray(l)[None]))

    _assert_no_host_sync(stack, state, *windows[0])
    stream = stack.stream_fn()

    lat_d = []
    for i, (p, l) in enumerate(windows):
        t0 = time.perf_counter()
        state, outs = stream(state, p, l)
        jax.block_until_ready(outs["tx_len"])
        dt = time.perf_counter() - t0
        if i == 0:
            assert bool(np.asarray(outs["alive"]).ravel()[0]), \
                "direct serve reply dropped"
        if i >= warmup:
            lat_d.append(dt * 1e6)
    served = int(np.asarray(state["apps"]["lm"]["served"]))
    assert served == warmup + n_requests, \
        f"direct path served {served}/{warmup + n_requests} requests"

    # ---- host-mediated baseline ------------------------------------------
    eng_h = ServeEngine(cfg, params, max_sessions=MAX_SESSIONS,
                        max_seq=MAX_SEQ)
    app = lm_server.LmServerApp(eng_h)
    for s in range(n_sessions):
        app.session_map[100 + s] = eng_h.new_session(prompts[s])

    lat_h = []
    for i, (p, l) in enumerate(windows):
        t0 = time.perf_counter()
        body, blen, ok = _parse_rx(p[0], l[0])        # device stack parse
        req = bytes(np.asarray(body[0, :int(blen[0])]).tobytes())  # sync
        reply = app.handle(req)                       # engine + host syncs
        F.udp_rpc_frame(IP_S, IP_C, LM_PORT, 5000,    # host reply framing
                        rpc.np_frame(rpc.MSG_LM_GENERATE, i, reply))
        dt = time.perf_counter() - t0
        if i >= warmup:
            lat_h.append(dt * 1e6)
        assert lm_server.reply_error(reply) is None

    d, h = _percentiles(lat_d), _percentiles(lat_h)
    return {
        "n_requests": n_requests, "n_sessions": n_sessions,
        "arch": cfg.name, "direct": d, "host": h,
        "speedup_p50": h["p50_us"] / d["p50_us"],
        "speedup_p99": h["p99_us"] / d["p99_us"],
        "speedup_p999": h["p999_us"] / d["p999_us"],
    }


def run():
    r = measure()
    d, h = r["direct"], r["host"]
    out = [row("rpc_tail_lm_direct", d["p50_us"],
               f"p99={d['p99_us']:.0f}us p999={d['p999_us']:.0f}us"),
           row("rpc_tail_lm_host", h["p50_us"],
               f"p99={h['p99_us']:.0f}us p999={h['p999_us']:.0f}us "
               f"speedup_p99={r['speedup_p99']:.2f}x")]
    append_trajectory(OUT_PATH, r)
    if r["speedup_p99"] < 2.0:
        raise RuntimeError(
            f"direct p99 {d['p99_us']:.0f}us is not <= 0.5x host-mediated "
            f"p99 {h['p99_us']:.0f}us (speedup {r['speedup_p99']:.2f}x, "
            f"gate: >= 2x)")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    run()
