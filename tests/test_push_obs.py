"""Push-mode observability: postcards, series ring, SLO watchdog.

Acceptance coverage:
  * under a netem Gilbert-Elliott burst, the watchdog emits exactly one
    MSG_ALERT edge per burst, in the same batch the drop-rate window
    crosses the threshold (hysteresis: no storm, re-arm after clear);
  * postcards decode to per-hop paths consistent with the flight
    recorder's trace rows, and obey the runtime sampling knobs;
  * the series ring serves per-window deltas (incl. wraparound) over
    OP_SERIES_READ, and OP_SLO_SET installs rules live;
  * the scanned region stays free of host callbacks with postcards +
    series + watchdog enabled;
  * the mirror's extra egress frames and the watchdog's alert path are
    deadlock-analyzed (data + ctrl NoCs).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import echo
from repro.core import control, deadlock
from repro.launch.certify import assert_no_host_callbacks
from repro.mgmt.console import MgmtConsole
from repro.net import frames as F, rpc
from repro.net.stack import UdpStack, udp_topology
from repro.netem.link import GilbertElliott, Link, LinkConfig
from repro.obs import collector, export, postcard, prom, reasons, series, slo

IP_C, IP_S = F.ip("10.0.0.2"), F.ip("10.0.0.1")
MGMT = 9909
APP_PORT = 7


def echo_frame(sport, req=1, payload=b"x"):
    return F.udp_rpc_frame(IP_C, IP_S, sport, APP_PORT,
                           rpc.np_frame(rpc.MSG_ECHO, req, payload))


def ip_corrupt(frame):
    fr = bytearray(frame)
    fr[F.l2_offset(frame) + 10] ^= 0xFF         # IP header checksum
    return bytes(fr)


def make_push_stack():
    apps = [echo.make(port=APP_PORT)]
    topo = udp_topology(apps)
    postcard.bind_mirror(topo, collector_ip=IP_C)
    slo.bind_watchdog(topo, collector_ip=IP_C)
    return UdpStack(apps, IP_S, topo=topo, mgmt_port=MGMT)


@pytest.fixture(scope="module")
def push_stack():
    return make_push_stack()


def stream(stack, state, batches, batch=4, width=256):
    arena = F.FrameArena(len(batches), batch, width)
    arena.fill([f for b in batches for f in b])
    return stack.stream_fn()(state, jnp.asarray(arena.payload),
                             jnp.asarray(arena.length))


def arm(stack, state, *, shift=0, window=1, rules=()):
    """Enable the recorder, set the window length, install rules; leaves
    the staleness batches behind so the next stream starts clean."""
    con = MgmtConsole(stack)
    state, r = con.set_trace(state, True, shift=shift)
    assert r["status"] == 1
    state, r = con.set_window(state, window)
    assert r["status"] == 1
    for (slot, metric, node, raise_thr, clear_thr) in rules:
        state, r = con.set_slo(state, slot, metric, node, raise_thr,
                               clear_thr)
        assert r["status"] == 1
    return con, state


# ---------------------------------------------------------------------------
# series ring (host-level unit + device readback)


def test_series_window_deltas_and_ring_wraparound():
    ser = series.make_series(2, windows=4)
    ser["win_len"] = jnp.asarray(1, jnp.int32)
    histo = jnp.zeros((3, 16), jnp.int32)
    for k in range(6):
        frames = jnp.asarray([k + 1, 1], jnp.int32)     # cumulative adds
        histo = histo.at[0, k % 16].add(1)
        ser = series.update(ser, frames, jnp.zeros(2, jnp.int32),
                            frames * 10, jnp.full((2,), 5 * (k + 1),
                                                  jnp.int32), histo)
    rows = series.series_rows(ser)
    # 6 windows closed into a 4-deep ring: only the last 4 survive
    assert int(ser["wr"]) == 6
    assert [w for w, _ in rows] == [2, 3, 4, 5]
    last_w, last = series.last_window(ser)
    assert last_w == 5
    # per-window deltas, not totals: window k saw exactly its own adds
    assert last[0, series.M_FRAMES] == 6 and last[1, series.M_FRAMES] == 1
    assert last[0, series.M_BYTES] == 60
    # retx arrives cumulative; the delta falls out of cum-prev
    assert last[0, series.M_RETX] == 5


def test_p99_bucket_picks_the_right_bucket():
    h = jnp.zeros((2, 16), jnp.int32).at[0, 3].set(99).at[0, 7].set(1)
    b = np.asarray(series.p99_bucket(h))
    assert b[0] == 3            # 99% of mass is at bucket 3
    assert b[1] == 0            # empty row -> 0


def test_series_read_over_mgmt(push_stack):
    stack = push_stack
    con, state = arm(stack, stack.init_state(), window=1)
    batches = [[echo_frame(5000 + i) for i in range(4)] for _ in range(2)]
    state, _ = stream(stack, state, batches)
    # age 0 = newest completed window = the 2nd stream batch (the mgmt
    # batches from arm() merged into an earlier window: win_len was
    # still the default while they ran)
    state, r = con.read_series(state, "udp_rx", age=0)
    s = r["series"]
    assert r["status"] == 2 + series.NUM_METRICS
    assert s["win_len"] == 1
    assert s["frames"] == 4 and s["drops"] == 0 and s["bytes"] > 0
    # invalid window age: served=0, no decode
    state, r = con.read_series(state, "udp_rx", age=1000)
    assert r["status"] == 0 and "series" not in r


# ---------------------------------------------------------------------------
# watchdog: GE burst -> exactly one edge, hysteresis, live rules


def _hysteresis_reference(drop_counts, raise_thr, clear_thr):
    """Python model of the device rule: per-window edge list."""
    edges, active = [], False
    for w, d in enumerate(drop_counts):
        if not active and d >= raise_thr:
            active = True
            edges.append(w)
        elif active and d <= clear_thr:
            active = False
    return edges


def test_watchdog_ge_burst_single_edge(push_stack):
    """Drive the stack through a Gilbert-Elliott loss schedule: frames
    the netem chain marks lost arrive corrupted, so ip_rx attributes an
    IP_CSUM drop.  The device watchdog must alert exactly once per
    burst, in the same batch the drop-rate window crosses."""
    stack = push_stack
    n_batches, batch = 12, 4
    link = Link(LinkConfig(gilbert=GilbertElliott(
        p_good_bad=0.2, p_bad_good=0.4), seed=11))
    sched = [[link._drop() for _ in range(batch)] for _ in range(n_batches)]
    drop_counts = [sum(b) for b in sched]
    edges = _hysteresis_reference(drop_counts, raise_thr=2, clear_thr=0)
    assert edges, "seed must produce at least one burst"

    con, state = arm(stack, stack.init_state(), window=1,
                     rules=[(0, "drops", "ip_rx", 2, 0)])
    batches = [[ip_corrupt(echo_frame(5000 + j)) if sched[b][j]
                else echo_frame(5000 + j) for j in range(batch)]
               for b in range(n_batches)]
    state, outs = stream(stack, state, batches)

    av = np.asarray(outs["alert_valid"])[:, 0]
    got = [int(b) for b in np.flatnonzero(av)]
    # exactly one edge per burst, each in the batch whose window crossed
    assert got == edges
    assert int(state["slo"]["alerts"]) == len(edges)

    alerts = [collector.decode_alert(f) for f in collector.harvest(
        outs["alert_payload"], outs["alert_len"], outs["alert_valid"])]
    assert len(alerts) == len(edges)
    a = alerts[0]
    assert a["metric"] == "drops"
    assert a["node"] == stack.pipeline.order.index("ip_rx")
    assert a["value"] == drop_counts[edges[0]]
    assert a["threshold"] == 2


def test_watchdog_hysteresis_rearm(push_stack):
    """A sustained burst is ONE alert; after the rate clears, the next
    burst re-arms and fires a second edge."""
    stack = push_stack
    good = [echo_frame(6000 + i) for i in range(4)]
    bad = [ip_corrupt(f) for f in good]
    con, state = arm(stack, stack.init_state(), window=1,
                     rules=[(0, "drops", "ip_rx", 3, 1)])
    batches = [good, bad, bad, bad, good, bad]
    state, outs = stream(stack, state, batches)
    av = np.asarray(outs["alert_valid"])[:, 0]
    assert list(np.flatnonzero(av)) == [1, 5]


def test_slo_set_validation_and_clear(push_stack):
    stack = push_stack
    con = MgmtConsole(stack)
    state = stack.init_state()
    state, r = con.set_slo(state, 99, "drops", "ip_rx", 2)   # bad slot
    assert r["status"] == 0
    state, r = con.set_slo(state, 1, "frames", "udp_rx", 100)
    assert r["status"] == 1
    state, r = con.clear_slo(state, 1)
    assert r["status"] == 1
    # two staleness batches later the table reflects the clear
    assert int(state["slo"]["enabled"][1]) == 0


# ---------------------------------------------------------------------------
# postcards: consistency with the flight recorder, sampling knobs


def test_postcards_match_flight_recorder(push_stack):
    stack = push_stack
    con, state = arm(stack, stack.init_state(), shift=0)
    frames = [echo_frame(5000 + i, req=i) for i in range(7)]
    frames.append(ip_corrupt(echo_frame(5007)))
    state, outs = stream(stack, state, [frames[:4], frames[4:]])

    cards = [collector.decode_postcard(f) for f in collector.harvest(
        outs["pc_payload"], outs["pc_len"], outs["pc_valid"])]
    assert len(cards) == 8 and all(c is not None for c in cards)

    by_fid = {row["frame_id"]: row
              for row in export.trace_rows(state["telemetry"]["obs"])}
    matched = 0
    for c in cards:
        row = by_fid.get(c["frame_id"])
        if row is None:
            continue                      # recorder ring may have wrapped
        matched += 1
        visited = [h["stage"] for h in c["hops"] if h["visited"]]
        assert visited == row["visited"]
        assert c["first_reason"] == row["drop_reason"]
        for h in c["hops"]:
            if h["visited"]:
                assert h["enter"] == row["enter"][h["stage"]]
                assert h["exit"] == row["exit"][h["stage"]]
    assert matched == 8
    # the corrupted frame's card says where and why it died
    dead = [c for c in cards if c["dropped"]]
    assert len(dead) == 1
    assert dead[0]["first_reason"] == reasons.IP_CSUM
    paths = collector.flow_paths(dead, stack.pipeline.order)
    (path_entries,) = paths.values()
    assert path_entries[0]["path"][-1] == "ip_rx"   # died at ip_rx
    assert path_entries[0]["first_reason"] == "ip_csum"


def test_postcards_obey_runtime_sampling(push_stack):
    stack = push_stack
    con, state = arm(stack, stack.init_state(), shift=2)   # 1 in 4
    fid0 = int(state["telemetry"]["obs"]["frame_ctr"])
    batches = [[echo_frame(5000 + i) for i in range(4)] for _ in range(2)]
    state, outs = stream(stack, state, batches)
    pv = np.asarray(outs["pc_valid"]).reshape(-1)
    fids = fid0 + np.arange(pv.size)
    assert (pv == ((fids & 3) == 0)).all()


def test_postcard_perfetto_merge(push_stack, tmp_path):
    stack = push_stack
    con, state = arm(stack, stack.init_state(), shift=0)
    state, outs = stream(stack, state,
                         [[echo_frame(5000 + i) for i in range(4)]])
    cards = [collector.decode_postcard(f) for f in collector.harvest(
        outs["pc_payload"], outs["pc_len"], outs["pc_valid"])]
    out = tmp_path / "merged.perfetto.json"
    n = collector.write_perfetto(str(out), cards, stack.pipeline.order,
                                 state=state, pipeline=stack.pipeline)
    import json
    ev = json.loads(out.read_text())["traceEvents"]
    assert len(ev) == n
    assert {e["pid"] for e in ev} == {0, 1}       # both halves present
    text = prom.render_state(state, stack.pipeline)
    assert "beehive_window_drops" in text and "beehive_slo_active" in text


# ---------------------------------------------------------------------------
# scanned region stays host-callback-free; NoC safety


def test_push_obs_adds_no_host_callbacks(push_stack):
    stack = push_stack
    con, state = arm(stack, stack.init_state(),
                     rules=[(0, "drops", "ip_rx", 2, 0)])
    arena = F.FrameArena(2, 2, 256)
    arena.fill([echo_frame(5000 + i) for i in range(4)])
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)

    prims = assert_no_host_callbacks(stack.run_stream, state, p, l)
    assert "scan" in prims


def test_mirror_and_alert_paths_are_deadlock_analyzed(push_stack):
    topo = push_stack.topo
    assert topo.has_tile("int_mirror") and topo.has_tile("watchdog")
    # the watchdog's in-band alert endpoint landed on the ctrl NoC
    assert topo.has_tile("watchdog.a")
    assert deadlock.analyze(topo, "data").ok
    assert deadlock.analyze(topo, "ctrl").ok
    # both taps are compiled, counted pipeline nodes
    assert "int_mirror" in push_stack.pipeline.order
    assert "watchdog" in push_stack.pipeline.order


def test_push_taps_do_not_perturb_the_datapath(push_stack):
    """tx/alive outputs with mirror+watchdog bound are bit-identical to
    the plain stack's."""
    frames = [echo_frame(5000 + i) for i in range(3)] + \
        [ip_corrupt(echo_frame(5003))]
    plain = UdpStack([echo.make(port=APP_PORT)], IP_S, mgmt_port=MGMT)
    arena = F.FrameArena(1, 4, 256)
    arena.fill(frames)
    p, l = jnp.asarray(arena.payload), jnp.asarray(arena.length)
    _, o_push = push_stack.run_stream(push_stack.init_state(), p, l)
    _, o_plain = plain.run_stream(plain.init_state(), p, l)
    for k in ("tx_payload", "tx_len", "alive"):
        assert (np.asarray(o_push[k]) == np.asarray(o_plain[k])).all()
