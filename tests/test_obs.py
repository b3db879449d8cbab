"""Device-resident observability layer (flight recorder, drop-reason
attribution, latency histograms, Perfetto export).

Acceptance coverage:
  * a runt UDP frame is attributed as exactly ONE `runt_udp` drop at the
    udp_rx tile (and distinct drop sites report distinct codes);
  * LOG_READ staleness window: a readback issued in batch k serves batch
    k-1's counters, under both `run` and `run_stream`;
  * recorder + histograms add zero host callbacks to the scanned region
    (jaxpr + HLO), and carrier outputs with tracing disabled are
    bit-identical to a `with_telemetry=False` stack;
  * TRACE_SET changes the live sampling rate with NO retrace;
  * the exporter writes valid Chrome trace-event JSON.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import echo
from repro.core import control
from repro.launch.certify import assert_no_host_callbacks
from repro.mgmt.console import MgmtConsole, command_frame, parse_response
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack, rpc_serve_topology
from repro.obs import export, flight, reasons

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
MGMT = 9909


def echo_frame(sport, req=1, port=7, payload=b"x"):
    return F.udp_rpc_frame(IP_C, IP_S, sport, port,
                           rpc.np_frame(rpc.MSG_ECHO, req, payload))


def runt_frame(sport=7001):
    """A UDP frame whose udp_len field claims fewer than 8 header bytes."""
    fr = bytearray(echo_frame(sport))
    off = F.l2_offset(bytes(fr)) + 20 + 4       # IP header, then udp_len
    fr[off:off + 2] = (4).to_bytes(2, "big")
    return bytes(fr)


def ip_corrupt_frame(sport=7002):
    fr = bytearray(echo_frame(sport))
    fr[F.l2_offset(bytes(fr)) + 10] ^= 0xFF     # IP header checksum
    return bytes(fr)


def make_stack(**kw):
    return UdpStack([echo.make(port=7)], IP_S, **kw)


def batch_of(frames, width=256):
    p, l = F.to_batch(frames, width)
    return jnp.asarray(p), jnp.asarray(l)


def node(stack, name):
    return stack.pipeline.order.index(name)


# ---------------------------------------------------------------------------
# drop-reason attribution (satellite: distinct code per drop site)


def test_runt_udp_is_exactly_one_runt_drop():
    stack = make_stack()
    st = stack.init_state()
    p, l = batch_of([echo_frame(5000), runt_frame(), echo_frame(5001)])
    st, *_ = stack.rx_tx(st, p, l)
    drops = np.asarray(st["telemetry"]["drops"])
    # exactly one RUNT_UDP, at udp_rx, and nowhere else in the table
    assert drops[node(stack, "udp_rx"), reasons.RUNT_UDP] == 1
    assert drops[:, reasons.RUNT_UDP].sum() == 1
    assert drops.sum() == 1


def test_distinct_sites_report_distinct_codes():
    stack = make_stack()
    st = stack.init_state()
    p, l = batch_of([echo_frame(5000), runt_frame(), ip_corrupt_frame()])
    st, *_ = stack.rx_tx(st, p, l)
    drops = np.asarray(st["telemetry"]["drops"])
    assert drops[node(stack, "ip_rx"), reasons.IP_CSUM] == 1
    assert drops[node(stack, "udp_rx"), reasons.RUNT_UDP] == 1
    assert drops.sum() == 2


def test_drop_read_over_mgmt_plane():
    stack = make_stack(mgmt_port=MGMT)
    con = MgmtConsole(stack)
    st = stack.init_state()
    p, l = batch_of([runt_frame(), echo_frame(5000)])
    st, *_ = stack.rx_tx(st, p, l)
    st, r = con.read_drops(st, "udp_rx")
    assert r["reasons"] == {"runt_udp": 1}


# ---------------------------------------------------------------------------
# LOG_READ staleness window (satellite): batch k serves batch k-1's row


def _log_read_frame(req_id=1):
    return command_frame(IP_C, IP_S, 5999, MGMT, control.OP_LOG_READ,
                         a=0, b=0, req_id=req_id)   # eth_rx, age 0


def test_log_read_staleness_window_run():
    stack = make_stack(mgmt_port=MGMT)
    st = stack.init_state()
    traffic = [echo_frame(5000 + i) for i in range(4)]
    st, *_ = stack.rx_tx(st, *batch_of(traffic))                 # batch 1
    st, q, ql, alive, info = stack.rx_tx(st, *batch_of([_log_read_frame()]))
    r = parse_response(bytes(np.asarray(q)[0][: int(ql[0])].tobytes()))
    assert r["status"] == 1
    # served row is batch 1's (step 1, 4 arrivals at eth_rx) even though
    # the read itself executed inside batch 2
    assert r["row"]["step"] == 1
    assert r["row"]["packets_in"] == len(traffic)


def test_log_read_staleness_window_run_stream():
    stack = make_stack(mgmt_port=MGMT)
    st = stack.init_state()
    traffic = [echo_frame(5000 + i) for i in range(4)]
    arena = F.FrameArena(2, 4, 256)
    arena.fill(traffic + [_log_read_frame()])
    st, outs = stack.run_stream(st, jnp.asarray(arena.payload),
                                jnp.asarray(arena.length))
    q = np.asarray(outs["tx_payload"])[1, 0]
    ql = int(np.asarray(outs["tx_len"])[1, 0])
    r = parse_response(bytes(q[:ql].tobytes()))
    assert r["status"] == 1
    assert r["row"]["step"] == 1
    assert r["row"]["packets_in"] == len(traffic)


# ---------------------------------------------------------------------------
# zero host callbacks + bit-identity (satellite)


def _enable(st, shift=0):
    st = dict(st)
    st["telemetry"] = dict(st["telemetry"])
    obs = dict(st["telemetry"]["obs"])
    obs["ctrl"] = {"enable": jnp.ones((), jnp.int32),
                   "shift": jnp.full((), shift, jnp.int32)}
    st["telemetry"]["obs"] = obs
    return st


def test_recorder_and_histos_add_no_host_callbacks():
    stack = make_stack()
    st = _enable(stack.init_state())
    arena = F.FrameArena(2, 2, 256)
    arena.fill([echo_frame(5000 + i) for i in range(4)])
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    fn = lambda s, pp, ll: stack.run_stream(s, pp, ll)
    prims = assert_no_host_callbacks(fn, st, p, l)
    assert "scan" in prims

    hlo = jax.jit(fn).lower(st, p, l).compile().as_text().lower()
    assert "infeed" not in hlo and "outfeed" not in hlo
    assert "send-to-host" not in hlo and "recv-from-host" not in hlo


def test_tracing_disabled_outputs_bit_identical_to_no_telemetry():
    """With the recorder disabled (the init default) the carrier outputs
    must match a stack with no telemetry at all, bit for bit — and a
    with_obs=False stack likewise: observability never perturbs data."""
    arena = F.FrameArena(2, 3, 256)
    arena.fill([echo_frame(5000 + i) for i in range(5)] + [runt_frame()])
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    outs = {}
    for key, kw in (("obs", {}), ("noobs", {"with_obs": False}),
                    ("notelem", {"with_telemetry": False})):
        stack = make_stack(**kw)
        _, o = stack.run_stream(stack.init_state(), p, l)
        outs[key] = o
    for k in ("tx_payload", "tx_len", "alive"):
        np.testing.assert_array_equal(np.asarray(outs["obs"][k]),
                                      np.asarray(outs["notelem"][k]),
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(outs["obs"][k]),
                                      np.asarray(outs["noobs"][k]),
                                      err_msg=k)


# ---------------------------------------------------------------------------
# live TRACE_SET: sampling knobs are runtime state, no retrace


def test_trace_set_live_without_retrace():
    stack = make_stack(mgmt_port=MGMT)
    traces = []

    def counted(st, p, l):
        traces.append(1)
        return stack.run_stream(st, p, l)

    fn = jax.jit(counted)
    width, batch = 256, 2

    def window(frames):
        arena = F.FrameArena(1, batch, width)
        arena.fill(frames)
        return jnp.asarray(arena.payload), jnp.asarray(arena.length)

    st = stack.init_state()
    st, _ = fn(st, *window([echo_frame(5000), echo_frame(5001)]))
    assert int(st["telemetry"]["obs"]["trace"].wr) == 0   # recorder off

    enable = command_frame(IP_C, IP_S, 5999, MGMT, control.OP_TRACE_SET,
                           a=1, b=0, req_id=7)            # record 1-in-1
    st, _ = fn(st, *window([enable, echo_frame(5002)]))
    st, _ = fn(st, *window([echo_frame(5003), echo_frame(5004)]))
    assert int(st["telemetry"]["obs"]["trace"].wr) == batch
    assert len(traces) == 1            # one compiled program served all


# ---------------------------------------------------------------------------
# flight-recorder contents + histograms + export


def test_flight_rows_record_visits_and_reasons():
    stack = make_stack()
    st = _enable(stack.init_state())
    p, l = batch_of([echo_frame(5000), runt_frame()])
    st, *_ = stack.rx_tx(st, p, l)
    rows = export.trace_rows(st["telemetry"]["obs"])
    assert [r["frame_id"] for r in rows] == [0, 1]
    good, runt = rows
    assert good["drop_reason"] == reasons.NONE
    assert node(stack, "eth_tx") in good["visited"]       # full traversal
    assert runt["drop_reason"] == reasons.RUNT_UDP
    assert node(stack, "udp_rx") in runt["visited"]
    assert node(stack, "eth_tx") not in runt["visited"]   # died at udp_rx
    for r in rows:
        for i in r["visited"]:
            assert r["exit"][i] > r["enter"][i]


def test_histograms_count_every_frame_when_enabled():
    stack = make_stack(mgmt_port=MGMT)
    con = MgmtConsole(stack)
    st = _enable(stack.init_state())
    n = 6
    st, *_ = stack.rx_tx(st, *batch_of([echo_frame(5000 + i)
                                        for i in range(n)]))
    histo = np.asarray(st["telemetry"]["obs"]["histo"])
    assert histo[node(stack, "eth_rx")].sum() == n        # per-stage row
    assert histo[-1].sum() == n                           # end-to-end row
    st, r = con.read_histo(st)                            # e2e over mgmt
    assert sum(r["table_row"]) >= n
    assert flight.percentile(r["table_row"], 0.5) >= 1


def test_perfetto_export_is_valid_trace_event_json(tmp_path):
    stack = make_stack()
    st = _enable(stack.init_state())
    st, *_ = stack.rx_tx(st, *batch_of([echo_frame(5000), runt_frame()]))
    path = str(tmp_path / "pipe.perfetto.json")
    n = export.write_perfetto(path, st, stack.pipeline)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert len(events) == n and n > 2
    slices = [e for e in events if e["ph"] == "X"]
    assert slices, "no complete slices exported"
    names = {e["name"] for e in slices}
    assert "eth_rx" in names and "udp_rx" in names
    for e in slices:
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["dur"] > 0 and {"pid", "tid"} <= set(e)


def test_perfetto_export_captures_rpc_serve_path(tmp_path):
    """Acceptance: a captured RPC-serve trace — the rs_serve tile shows
    up as a slice in the exported trace, and an app-rejected request is
    attributed to it."""
    stack = UdpStack([], IP_S, topo=rpc_serve_topology(
        [("rs", "rs_serve", rpc.MSG_RS_ENCODE)]))
    st = _enable(stack.init_state())
    rng = np.random.default_rng(0)
    good = F.udp_rpc_frame(IP_C, IP_S, 5000, 9400,
                           rpc.np_frame(rpc.MSG_RS_ENCODE, 0,
                                        rng.bytes(4096)))
    bad = F.udp_rpc_frame(IP_C, IP_S, 5001, 9400,
                          rpc.np_frame(rpc.MSG_RS_ENCODE, 1, b"short"))
    st, *_ = stack.rx_tx(st, *batch_of([good, bad], width=4400))
    drops = np.asarray(st["telemetry"]["drops"])
    assert drops[node(stack, "rs"), reasons.APP_BAD_REQ] == 1
    path = str(tmp_path / "serve.perfetto.json")
    export.write_perfetto(path, st, stack.pipeline)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert "rs" in {e["name"] for e in events if e["ph"] == "X"}
