"""The benchmark's data and yardstick, on the CPU: every cell resolves by
name, the contract's shape holds, mixes follow the seed, the client's
frames and the references agree with the program's own frame code, and the
trace reduction reads busy time, kernel time and idle gaps right."""
from __future__ import annotations

import json
import os
import re

import numpy as np
import pytest

from bench import harness, peaks, trace_reduce, traffic, wire

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    fours = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(fours) <= max(1, len(CELLS) // 2)
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(CELLS)
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    wl = {w["name"]: w for w in BENCH["workloads"]}[cell]
    cfg, mod = harness.load_config(wl["config"])
    for fn in ("build", "request_body_len", "requests", "reply_cap",
               "served"):
        assert callable(getattr(mod, fn)), fn
    # a configuration judges its replies itself, or builds them for the
    # harness's byte comparison
    assert callable(getattr(mod, "judge", None)
                    or getattr(mod, "expected", None))
    entry = {c["name"]: c for c in BENCH["configs"]}[wl["config"]]
    assert entry["file"] == f"bench/configs/{wl['config']}.json"
    assert cfg["name"] == wl["config"]
    assert traffic.load_mix(wl["traffic"])["loop"] in ("closed", "open")
    e2e = harness.cell_metrics(BENCH, cell, trace=False)
    layer = harness.cell_metrics(BENCH, cell, trace=True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
        assert callable(harness.load_metric(m["name"]).read)
    for m in layer:
        assert m["moves"] in {x["name"] for x in e2e}


@pytest.mark.parametrize("cell", CELLS)
def test_mix_follows_the_seed(cell):
    wl = {w["name"]: w for w in BENCH["workloads"]}[cell]
    cfg, mod = harness.load_config(wl["config"])
    mix = traffic.load_mix(wl["traffic"])
    shards = cfg["shards"]
    a = harness.make_traffic(mix, cfg, mod, shards, 64, 2**33 + 5, 0.5)
    b = harness.make_traffic(mix, cfg, mod, shards, 64, 2**33 + 5, 0.5)
    c = harness.make_traffic(mix, cfg, mod, shards, 64, 7, 0.5)
    assert a.frames == b.frames
    assert a.frames != c.frames
    # another seed: the same work in another order
    assert sorted(map(len, a.frames)) == sorted(map(len, c.frames))
    assert np.array_equal(np.sort(a.kind), np.sort(c.kind))
    if a.due is not None:
        assert np.allclose(np.sort(np.diff(a.due, prepend=0)),
                           np.sort(np.diff(c.due, prepend=0)))


def test_rss_windows_hold_whole_flows_on_their_shard():
    cfg, mod = harness.load_config("udp_echo_rss4")
    mix = traffic.load_mix("64B")
    pool = harness.make_traffic(mix, cfg, mod, 4, 512, 3, 1.0)
    for w in pool.windows:
        for s in range(4):
            ports = pool.client_port[w[s]]
            assert np.all(ports % 4 == s)
            # each flow's frames sit together
            changes = np.flatnonzero(np.diff(ports)) + 1
            starts = ports[np.concatenate([[0], changes])]
            assert len(starts) == len(set(starts.tolist()))
    bad = np.bincount(pool.kind[pool.windows[0, 2]], minlength=3)
    assert bad[traffic.IP_CSUM] == 8 and bad[traffic.NO_ROUTE] == 8


def test_imix_sizes_in_ratio():
    mix = traffic.load_mix("imix")
    cfg, mod = harness.load_config("udp_echo")
    pool = harness.make_traffic(mix, cfg, mod, 1, 1200, 1, 1.0)
    n = np.bincount(pool.body_len[pool.windows[0, 0]] + wire.HDR)
    assert (n[78], n[590], n[1514]) == (700, 400, 100)


def _echo_pool(mix, seed=3, rows=256, shards=1, seconds=1.0):
    cfg, mod = harness.load_config("udp_echo")
    return harness.make_traffic(mix, cfg, mod, shards, rows, seed, seconds)


def test_zipf_flows_follow_the_seed_and_skew():
    mix = dict(traffic.load_mix("64B"), flow_zipf=0.99)
    a, b = _echo_pool(mix), _echo_pool(mix)
    assert a.frames == b.frames
    assert a.frames != _echo_pool(mix, seed=4).frames
    ports = a.client_port[a.windows.ravel()] - mix["base_port"]
    n = np.bincount(ports, minlength=mix["flows"])
    # rank 1 of 10,000 under theta 0.99 draws about 10 % of the frames
    assert n[0] > 50 and n[0] > 20 * np.median(n)
    plain = _echo_pool(traffic.load_mix("64B"))
    assert np.bincount(plain.client_port - mix["base_port"]).max() < 10


def test_on_off_arrivals_keep_the_mean_rate():
    mix = dict(traffic.load_mix("open"), rate_per_s=1000.0,
               arrivals={"on_off": [0.05, 0.15]})
    rng = np.random.default_rng(5)
    t = traffic.arrival_times(mix, 2000, rng)
    assert np.all(np.diff(t) > 0)
    # 2000 at 1000/s: ten on/off cycles, the last arrival in the tenth on
    assert 1.8 < t[-1] < 1.85 + 1e-3
    assert np.all(np.mod(t, 0.2) < 0.05 + 1e-12)       # none while off
    again = traffic.arrival_times(mix, 2000, np.random.default_rng(5))
    other = traffic.arrival_times(mix, 2000, np.random.default_rng(6))
    assert np.array_equal(t, again) and not np.array_equal(t, other)
    poisson = traffic.arrival_times(traffic.load_mix("open"), 8000, rng)
    assert poisson[-1] == pytest.approx(1.0, rel=0.02)
    with pytest.raises(ValueError):
        traffic.arrival_times(dict(mix, arrivals="uniform"), 10, rng)


def test_mix_may_name_a_generator_of_its_own(tmp_path, monkeypatch):
    """A mix the general generator cannot say brings its own module, by
    name, beside the mix's data."""
    (tmp_path / "fixed.py").write_text(
        "from bench import traffic\n"
        "def make_pool(mix, cfg, shards, rows, seed, seconds, body_len):\n"
        "    mix = dict(mix, generator=None, frame_sizes=[mix['size']])\n"
        "    return traffic.make_pool(mix, cfg, shards, rows, seed,\n"
        "                             seconds, body_len)\n")
    mix = dict(traffic.load_mix("64B"), generator="fixed", size=100)
    monkeypatch.setattr(traffic, "MIXES", str(tmp_path))
    pool = _echo_pool(mix)
    assert sorted(set(map(len, pool.frames))) == [100]
    assert traffic.generator({"loop": "closed"}) is traffic.make_pool


def test_configuration_frames_its_replies():
    """The configuration builds the replies it expects: the echo's are
    each request's own bytes with addresses and ports swapped."""
    cfg, mod = harness.load_config("udp_echo")
    pool = _echo_pool(traffic.load_mix("imix"), rows=64)
    cap = mod.reply_cap(cfg, pool)
    exp, exp_len, body_len = mod.expected(cfg, pool, cap)
    for i in range(len(pool.frames)):
        req = np.frombuffer(pool.frames[i], np.uint8)
        if pool.kind[i] != traffic.OK:
            assert exp_len[i] == 0 and body_len[i] == 0
            continue
        rep = exp[i, :exp_len[i]]
        assert len(rep) == len(req) and body_len[i] == len(req) - wire.HDR
        assert np.array_equal(rep[wire.HDR:], req[wire.HDR:])
        assert np.array_equal(rep[0:6], req[6:12])          # MACs swapped
        assert np.array_equal(rep[26:30], req[30:34])       # IPs swapped
        assert np.array_equal(rep[34:36], req[36:38])       # ports swapped


def test_wire_matches_the_programs_frames():
    from repro.net import frames as F, rpc
    rng = np.random.default_rng(0)
    blen = np.array([0, 1, 13, 200, 1463])
    body = rng.integers(0, 256, (5, 1463), dtype=np.uint8)
    buf, flen = wire.build(wire.ip4("10.1.2.3"), wire.ip4("10.0.0.1"),
                           np.array([10000, 10001, 10002, 65535, 1]), 7,
                           1, np.arange(5) * 77777, body, blen,
                           wire.mac("02:00:00:00:00:02"),
                           wire.mac("02:00:00:00:00:01"), 1536)
    for i in range(5):
        want = F.udp_rpc_frame(F.ip("10.1.2.3"), F.ip("10.0.0.1"),
                               int([10000, 10001, 10002, 65535, 1][i]), 7,
                               rpc.np_frame(1, i * 77777,
                                            body[i, :blen[i]].tobytes()))
        assert buf[i, :flen[i]].tobytes() == want
        assert not buf[i, flen[i]:].any()


def test_rs_reference_matches_program_oracle():
    from repro.kernels.rs_encode import gf
    from repro.kernels.rs_encode.ref import rs_encode_np
    cfg, mod = harness.load_config("rs_encode")
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (3, 4096), dtype=np.uint8)
    got = mod.reply_body(cfg, data)
    gm = gf.generator_matrix(8, 2)
    for r in range(3):
        want = rs_encode_np(data[r].reshape(8, 512), gm).reshape(-1)
        assert np.array_equal(got[r], want)
    assert got.shape == (3, 1024)


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_percentile_counts_failures_as_missing():
    from bench.readers import percentile_ms
    lat = np.array([0.001] * 98 + [np.inf, np.inf])
    ctx = {"latency_s": lat, "window_s": 10.0, "give_up_s": 60.0}
    assert percentile_ms(ctx, 50) == pytest.approx(1.0)
    assert percentile_ms(ctx, 99) == pytest.approx(70e3)


# ---- trace reduction


def _planes(dev_ops, host):
    return {"/device:TPU:0": {"XLA Ops": dev_ops, "XLA Modules": []},
            "/host:CPU": {"python": host}}


def test_union_clip_and_gap_attribution():
    ops = [("fusion.1", 100, 50), ("fusion.2", 120, 60),   # 100..180
           ("_rs_kernel", 300, 100),                       # 300..400
           ("fusion.3", 950, 200)]                         # 950..1150
    host = [("bench_window", 50, 1000),                    # 50..1050
            ("fill", 180, 100), ("put", 280, 20), ("readback", 400, 600)]
    s = trace_reduce.reduce_events(_planes(ops, host), "bench_window",
                                   harness.SPANS)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((80 + 100 + 100) * 1e-9)
    assert s.op_s["_rs_kernel"] == pytest.approx(100e-9)
    gaps = dict(s.idle_gaps)
    # 50..100 none; 180..280 fill ; 280..300 put; 400..950 readback
    assert gaps["fill"] == pytest.approx(100e-9)
    assert gaps["put"] == pytest.approx(20e-9)
    assert gaps["readback"] == pytest.approx(550e-9)
    assert gaps["none"] == pytest.approx(50e-9)
    assert sum(gaps.values()) + s.busy_s == pytest.approx(s.window_s)
    bd = s.breakdown()
    assert bd["device_ops"][0][0] in ("fusion.3", "_rs_kernel")
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_rs_roofline_reads_the_pallas_custom_call():
    reader = harness.load_metric("rs_encode_roofline")
    kernel = ("%branch_0_fun.1 = u32[2,8192]{1,0:T(2,128)S(1)} custom-call("
              "u32[8,8192]{1,0:T(8,128)S(1)} %copy.261), custom_call_target="
              "\"tpu_custom_call\", frontend_attributes={kernel_metadata={}}")
    other = "%fusion.312 = u8[270336]{0} fusion(u8[64,4224]{1,0} %p), kind=kCustom"
    ops = [(kernel, 0, 1000), (other, 1000, 9000), (kernel, 10000, 1000)]
    planes = _planes(ops, [("bench_window", 0, 30000)])
    planes["/device:TPU:0"]["XLA Modules"] = [
        ("jit_run_stream(1)", 0, 5000),         # began before the span
        ("jit_run_stream(1)", 5000, 10000),      # run 0: 5000..15000
        ("jit_run_stream(1)", 15000, 5000),      # run 1: the last recorded
        ("jit_other", 20000, 10)]
    planes["/host:CPU"]["python"][0] = ("bench_window", 1, 30000)
    s = trace_reduce.reduce_events(planes, "bench_window", ())
    assert s.runs == [[(0, 10000e-9)]]
    # only the kernel call inside run 0 counts (10000..11000)
    assert reader.kernel_s(s) == pytest.approx(1000e-9)
    ctx = {"trace": s, "traced_rows": [np.array([64]), np.array([64])],
           "traced_ok": [np.array([100]), np.array([7])],
           "device_kind": "TPU v5 lite"}
    least = 100 * 5120 / 819e9
    assert reader.read(ctx) == pytest.approx(100 * least / 1000e-9)
    assert reader.read(dict(ctx, traced_ok=[np.array([0])] * 2)) is None
    from bench import readers
    assert readers.device_ns_per_frame(ctx) == pytest.approx(10000 / 64)
    assert harness.load_metric("device_ms_per_window.rpc").read(ctx) == \
        pytest.approx(10000e-6)


def test_idle_counts_from_the_first_program_run():
    """Before the device's first run of the stream program in the span
    the pipeline is empty, waiting for the host's first window: that
    stretch is neither busy nor idle."""
    ops = [("warm", 10, 20), ("fusion.1", 400, 100), ("fusion.2", 700, 100)]
    host = [("bench_window", 0, 1000), ("fill", 0, 390), ("put", 500, 200)]
    planes = _planes(ops, host)
    planes["/device:TPU:0"]["XLA Modules"] = [
        ("jit_run_stream(1)", 400, 100), ("jit_run_stream(1)", 700, 100)]
    s = trace_reduce.reduce_events(planes, "bench_window", harness.SPANS)
    assert s.window_s == pytest.approx(600e-9)       # 400..1000
    assert s.busy_s == pytest.approx(200e-9)
    gaps = dict(s.idle_gaps)
    assert gaps["put"] == pytest.approx(200e-9)      # 500..700
    assert gaps["none"] == pytest.approx(200e-9)     # 800..1000
    assert gaps["fill"] == 0.0


def test_busy_is_mean_over_devices_and_total_is_sum():
    planes = _planes([("a", 0, 100)], [("bench_window", 0, 200)])
    planes["/device:TPU:1"] = {"XLA Ops": [("a", 0, 50), ("b", 25, 50)],
                               "Async XLA Ops": [("copy-start", 100, 10)]}
    s = trace_reduce.reduce_events(planes, "bench_window", ())
    assert s.devices == 2
    assert s.busy_s_total == pytest.approx(185e-9)
    assert s.busy_s == pytest.approx(92.5e-9)


def test_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/host:CPU": {"python": []}},
                                   "bench_window", ())


RECORDED = os.path.join(harness.HERE, "testdata")


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite through rs.open's path (a 1 x
    8-row arena), cut to its device lines and the benchmark's spans;
    the json beside it holds what the reduction read from the whole
    recording."""
    meta = json.load(open(os.path.join(RECORDED, "rs_open.json")))
    s = trace_reduce.summarize(os.path.join(RECORDED, "rs_open.xplane.pb"),
                               harness.WINDOW_SPAN, harness.SPANS)
    assert s.devices == meta["devices"] == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.window_s == pytest.approx(meta["window_s"], rel=1e-9)
    assert s.busy_s == pytest.approx(meta["busy_s"], rel=1e-9)
    assert [[tuple(r) for r in d] for d in meta["runs"]] == s.runs
    assert len(s.runs[0]) >= 1
    reader = harness.load_metric("rs_encode_roofline")
    assert reader.kernel_s(s) == pytest.approx(meta["rs_kernel_s"], rel=1e-9)
    assert reader.kernel_s(s) > 0
    # the kernel as the chip's trace names it: a tpu_custom_call
    assert any("tpu_custom_call" in k for k in s.op_s)
