"""Direct-attached application serving through the compiled stack.

Acceptance coverage for the serving tentpole:
  * `rpc_msg` dispatch: udp_rx routes on the RPC msg_type to app tiles
    declared in the topology like any protocol tile (runtime-rewritable
    CAM, unmatched types drop);
  * `rs_serve` parity vs the numpy RS oracle — accelerator compute in
    the reply path with no host round trip;
  * `lm_serve` inside `run_stream`: device-resident session/KV state in
    the scan carry produces the exact token stream of the host-driven
    `ServeEngine.generate`, one request per token;
  * malformed / unknown-session / duplicate requests get error replies
    (never raise) and only valid requests advance session state;
  * zero host transfers inside the compiled serve program (jaxpr + HLO,
    mirroring tests/test_stream.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import lm_server
from repro.configs.serve_smoke import serve_config
from repro.kernels.rs_encode import gf
from repro.kernels.rs_encode.ref import rs_encode_np
from repro.launch.certify import assert_no_host_callbacks
from repro.models import model
from repro.net import eth, frames as F, ipv4, rpc, udp
from repro.net.stack import UdpStack, rpc_serve_topology
from repro.serve.engine import ServeEngine

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
PORT = 9400


def serve_frame(msg, req_id, body, sport=5000):
    return F.udp_rpc_frame(IP_C, IP_S, sport, PORT,
                           rpc.np_frame(msg, req_id, body))


def parse_reply(q, ql, i):
    p, l, m = eth.parse(q, ql)
    p, l, m2, ok1 = ipv4.parse(p, l)
    m.update(m2)
    p, l, m3, ok2 = udp.parse(p, l, m)
    body, blen, rmeta, ok3 = rpc.parse(p, l)
    assert bool(ok1[i]) and bool(ok2[i]) and bool(ok3[i])
    return bytes(np.asarray(body[i, :blen[i]]).tobytes())


# ---------------------------------------------------------------------------
# rpc_msg dispatch + rs_serve (no model: fast lane)


def test_rs_serve_direct_dispatch_and_parity():
    stack = UdpStack([], IP_S, topo=rpc_serve_topology(
        [("rs", "rs_serve", rpc.MSG_RS_ENCODE)]))
    state = stack.init_state()
    # msg_type routing is a runtime-rewritable CAM like any keyed route
    assert "udp_rx:rpc_msg" in state["routes"]

    rng = np.random.default_rng(0)
    block = rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
    frames = [serve_frame(rpc.MSG_RS_ENCODE, 0, block),
              serve_frame(rpc.MSG_RS_ENCODE, 1, b"short"),   # runt request
              serve_frame(rpc.MSG_ECHO, 2, b"x")]            # unrouted type
    p, l = F.to_batch(frames, 4400)
    state, q, ql, alive, info = stack.rx_tx(state, jnp.asarray(p),
                                            jnp.asarray(l))
    assert bool(alive.all())
    served = np.asarray(info["rs"])
    assert served.tolist() == [True, False, False]   # runt + unrouted type

    parity = parse_reply(q, ql, 0)
    assert len(parity) == 1024
    data = np.frombuffer(block, np.uint8).reshape(8, 512)
    want = rs_encode_np(data, gf.generator_matrix(8, 2)).reshape(-1)
    np.testing.assert_array_equal(np.frombuffer(parity, np.uint8), want)

    assert parse_reply(q, ql, 1) == b""        # runt: empty error reply
    assert int(np.asarray(state["apps"]["rs"]["ops"])) == 1
    assert int(np.asarray(state["apps"]["rs"]["bytes"])) == 4096


# ---------------------------------------------------------------------------
# lm_serve: direct-attached decode inside run_stream (model: slow lane)


@pytest.fixture(scope="module")
def serve_setup():
    cfg = serve_config()
    params = model.init_params(cfg, jax.random.key(0))
    return cfg, params


def make_serve_stack(cfg, params, max_sessions=2, max_seq=32):
    lm = lm_server.make_tile(cfg, params, max_sessions=max_sessions,
                             max_seq=max_seq)
    stack = UdpStack([lm], IP_S, topo=rpc_serve_topology(
        [("lm", "lm_serve", rpc.MSG_LM_GENERATE)]))
    return stack


def lm_frame(session, req_id):
    return serve_frame(rpc.MSG_LM_GENERATE, req_id,
                       lm_server.encode_request(session, 1, []))


@pytest.mark.slow
def test_lm_serve_stream_matches_engine(serve_setup):
    """The tentpole equivalence: N single-request windows through
    `run_stream` (session KV in the scan carry, one decode per request)
    produce exactly `ServeEngine.generate(sid, N)`."""
    cfg, params = serve_setup
    eng = ServeEngine(cfg, params, max_sessions=2, max_seq=32)
    prompt = np.arange(1, 7, dtype=np.int32)
    sid = eng.new_session(prompt)
    ref = ServeEngine(cfg, params, max_sessions=2, max_seq=32)
    want = ref.generate(ref.new_session(prompt), 4)

    stack = make_serve_stack(cfg, params)
    state = stack.init_state()
    state["apps"]["lm"] = lm_server.adopt_engine(state["apps"]["lm"], eng,
                                                 {42: sid})
    arena = F.FrameArena(4, 1, 160)
    arena.fill([lm_frame(42, i) for i in range(4)])
    state, outs = stack.run_stream(state, jnp.asarray(arena.payload),
                                   jnp.asarray(arena.length))
    assert bool(np.asarray(outs["alive"]).all())
    got = []
    for i in range(4):
        reply = parse_reply(outs["tx_payload"][i], outs["tx_len"][i], 0)
        s, toks, ok = lm_server.decode_reply(reply)
        assert ok and s == 42 and lm_server.reply_error(reply) is None
        got += toks
    assert got == want
    assert int(np.asarray(state["apps"]["lm"]["served"])) == 4


@pytest.mark.slow
def test_lm_serve_error_replies_and_coalescing(serve_setup):
    """One batch mixing valid / duplicate / unknown-session / truncated
    requests: errors come back as sentinel replies (nothing raises, the
    batch stays alive) and only the valid session advances — once."""
    cfg, params = serve_setup
    eng = ServeEngine(cfg, params, max_sessions=2, max_seq=32)
    sid = eng.new_session(np.arange(1, 7, dtype=np.int32))

    stack = make_serve_stack(cfg, params)
    state = stack.init_state()
    state["apps"]["lm"] = lm_server.adopt_engine(state["apps"]["lm"], eng,
                                                 {42: sid})
    pos0 = int(np.asarray(state["apps"]["lm"]["pos"])[sid])

    frames = [lm_frame(42, 0),
              lm_frame(42, 1),                       # duplicate: coalesces
              lm_frame(777, 2),                      # unknown session
              serve_frame(rpc.MSG_LM_GENERATE, 3,    # truncated request
                          lm_server.encode_request(43, 1, [])[:4])]
    p, l = F.to_batch(frames, 160)
    state, q, ql, alive, info = stack.rx_tx(state, jnp.asarray(p),
                                            jnp.asarray(l))
    assert bool(alive.all())

    r0 = lm_server.decode_reply(parse_reply(q, ql, 0))
    r1 = lm_server.decode_reply(parse_reply(q, ql, 1))
    assert r0 == r1 and r0[2] and len(r0[1]) == 1    # same token, once
    assert lm_server.reply_error(parse_reply(q, ql, 2)) == \
        lm_server.ERR_NO_SESSION
    assert lm_server.reply_error(parse_reply(q, ql, 3)) == \
        lm_server.ERR_BAD_REQUEST

    st = state["apps"]["lm"]
    assert int(np.asarray(st["pos"])[sid]) == pos0 + 1   # advanced ONCE
    assert int(np.asarray(st["served"])) == 2            # both valid rows


def test_lm_serve_weights_are_program_arguments(serve_setup):
    """The weights reach the stream program through the tile state, an
    argument, and are never folded into it as constants."""
    cfg, params = serve_setup
    stack = make_serve_stack(cfg, params)
    state = stack.init_state()
    arena = F.FrameArena(1, 1, 160)
    closed = jax.make_jaxpr(stack.run_stream)(
        state, jnp.asarray(arena.payload), jnp.asarray(arena.length))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    const_bytes = sum(np.asarray(c).nbytes for c in closed.consts)
    assert const_bytes < param_bytes // 10
    jax.tree.map(np.testing.assert_array_equal,
                 state["apps"]["lm"]["params"], params)


@pytest.mark.slow
def test_serve_stream_zero_host_transfers(serve_setup):
    """The direct-attached acceptance bar: the compiled serve program —
    parse tiles, lm_serve decode, reply framing — contains no host
    callbacks or transfers inside the scanned region."""
    cfg, params = serve_setup
    stack = make_serve_stack(cfg, params)
    state = stack.init_state()
    arena = F.FrameArena(2, 1, 160)
    arena.fill([lm_frame(42, i) for i in range(2)])
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    fn = lambda st, pp, ll: stack.run_stream(st, pp, ll)
    prims = assert_no_host_callbacks(fn, state, p, l)
    assert "scan" in prims

    hlo = jax.jit(fn).lower(state, p, l).compile().as_text()
    low = hlo.lower()
    assert "infeed" not in low and "outfeed" not in low
    assert "send-to-host" not in low and "recv-from-host" not in low
    assert "while" in low
