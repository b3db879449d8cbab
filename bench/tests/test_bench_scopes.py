"""The readers of the program's named scopes and host counters, on the
CPU: the scope map from compiled HLO, device seconds by scope over a
synthetic trace, each new metric on a synthetic context, and the host
counters of a real run on the CPU."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import harness, scopes, trace_reduce
from bench.tests import cpu_run

HLO = """HloModule jit_run_stream, is_scheduled=true

FileNames
1 "bytesops.py"

%fused_computation.1 (param_0.1: u8[8,64]) -> u8[8,64] {
  %param_0.1 = u8[8,64]{1,0} parameter(0)
  %constant.9 = s32[] constant(0), metadata={op_name="jit(run_stream)/while/body/obs/drops/and"}
  %gather.1 = u8[8,64]{1,0} gather(%param_0.1), metadata={op_name="jit(run_stream)/while/body/closed_call/stage/eth_rx/bytes/shift/gather" stack_frame_id=3}
  ROOT %select.1 = u8[8,64]{1,0} select(%gather.1), metadata={op_name="jit(run_stream)/while/body/closed_call/stage/ip_rx/bytes/shift/select_n" stack_frame_id=4}
}

%fused_computation.2 (param_0.2: s32[64]) -> s32[64] {
  %param_0.2 = s32[64]{0} parameter(0)
  %pad.2 = s32[64]{0} pad(%param_0.2), metadata={op_name="jit(run_stream)/while/body/stage/udp_tx/bytes/csum/pad" stack_frame_id=5}
  ROOT %clamp.2 = s32[64]{0} clamp(%pad.2), metadata={op_name="gather" stack_frame_id=9}
}

%body (p: (s32[], u8[8,64])) -> (s32[], u8[8,64]) {
  %p = (s32[], u8[8,64]) parameter(0)
  %fusion.1 = u8[8,64]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(run_stream)/while/body/closed_call/stage/ip_rx/bytes/shift/select_n"}
  %pad_clamp_fusion.2 = s32[64]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="gather" stack_frame_id=9}
  %gather.7 = u8[8]{0} gather(%p), metadata={op_name="gather" stack_frame_id=4}
  %select.8 = u8[8]{0} select(%gather.7), metadata={op_name="jit(run_stream)/while/body/stage/ip_rx/bytes/shift/select_n"}
  %neg.6 = s32[8]{0} negate(%p), metadata={op_name="jit(run_stream)/while/body/stage/udp_tx/bytes/shift/neg" stack_frame_id=4}
  %add.3 = s32[8]{0} add(%p), metadata={op_name="jit(run_stream)/while/body/obs/counters/add"}
  %copy.4 = u8[8,64]{1,0} copy(%p), metadata={op_name="jit(run_stream)/while/body/closed_call"}
  ROOT %tuple.5 = (s32[], u8[8,64]) tuple(%add.3, %fusion.1)
}

ENTRY %main.9 (x.1: u8[8,64]) -> u8[8,64] {
  %x.1 = u8[8,64]{1,0} parameter(0), metadata={op_name="x"}
  ROOT %while.1 = (s32[], u8[8,64]) while(%x.1), condition=%cond, body=%body, metadata={op_name="jit(run_stream)/while"}
}
"""


def test_scope_path_keeps_the_programs_own_scopes():
    assert scopes.scope_path(
        "jit(run_stream)/while/body/closed_call/stage/ip_rx/bytes/shift/"
        "jit(take_along_axis)/gather") == "stage/ip_rx/bytes/shift"
    assert scopes.scope_path("jit(f)/obs/recorder/cumsum") == "obs/recorder"
    assert scopes.scope_path("jit(f)/mgmt/commit/select_n") == "mgmt/commit"
    assert scopes.scope_path("jit(f)/while/body/closed_call") == "unscoped"
    assert scopes.outer("stage/udp_rx/bytes/csum") == "stage/udp_rx"
    assert scopes.inner_of("stage/udp_rx/bytes/csum") == "bytes/csum"
    assert scopes.common({"stage/a/bytes/shift", "stage/b/bytes/shift"}) \
        == "bytes/shift"
    assert scopes.common({"stage/a/bytes/shift", "stage/a"}) == "stage/a"
    assert scopes.common(set()) == "unscoped"


def test_hlo_map_takes_fusion_roots_and_resolves_bare_ops():
    names, fused = scopes.parse_hlo(HLO)
    # a fusion takes its root's scope, not its own op_name's
    assert names["fusion.1"] == "stage/ip_rx/bytes/shift"
    assert sorted(set(fused["fusion.1"])) == [
        "stage/eth_rx/bytes/shift", "stage/ip_rx/bytes/shift"]
    # a bare "gather" takes what its source frame's scoped ops share
    # (bytes/shift, called from two stages), then its user's stage
    assert names["gather.7"] == "stage/ip_rx/bytes/shift"
    assert scopes.common({"stage/ip_rx/bytes/shift",
                          "stage/udp_tx/bytes/shift"}) == "bytes/shift"
    # a fusion whose root stays bare takes what its fused ops share
    assert names["pad_clamp_fusion.2"] == "stage/udp_tx/bytes/csum"
    assert names["copy.4"] == "unscoped"
    assert names["add.3"] == "obs/counters"
    mixed = scopes.mixed_fusions({"fusion.1 fusion": 2.0}, names, fused)
    assert mixed == [("fusion.1 fusion", 2.0, "stage/ip_rx/bytes/shift",
                      ["stage/eth_rx", "stage/ip_rx"])]


def _trace():
    """One device, two whole program runs (0..1000, 1000..2000) and a
    last one cut short; op events named as a TPU names them."""
    def ev(inst, op, start, dur):
        return (f"%{inst} = u8[8,64]{{1,0}} {op}(%p), kind=kLoop", start, dur)
    ops = [ev("while.1", "while", 0, 1000),
           ev("fusion.1", "fusion", 0, 600), ev("add.3", "add", 600, 100),
           ev("pad_clamp_fusion.2", "fusion", 700, 100),
           ev("copy.4", "copy", 800, 100),
           ev("while.1", "while", 1000, 1000),
           ev("fusion.1", "fusion", 1000, 600), ev("add.3", "add", 1600, 50),
           ev("gather.7", "gather", 1650, 50),
           ev("fusion.1", "fusion", 2000, 600)]     # in the cut-short run
    planes = {"/device:TPU:0": {
        "XLA Ops": ops,
        "XLA Modules": [("jit_run_stream(1)", 0, 1000),
                        ("jit_run_stream(1)", 1000, 1000),
                        ("jit_run_stream(1)", 2000, 700)]},
        "/host:CPU": {"python": [("bench_window", -1, 3001),
                                 ("fill", 2700, 100)]}}
    return trace_reduce.reduce_events(planes, harness.WINDOW_SPAN,
                                      harness.SPANS)


def test_scope_seconds_over_the_reduced_trace():
    s = _trace()
    # the reduction itself is as it was: whole runs, their ops, busy, idle
    assert s.runs == [[(0, pytest.approx(1000e-9)),
                       (1, pytest.approx(1000e-9))]]
    assert s.op_s["fusion.1 fusion"] == pytest.approx(1200e-9)
    assert s.op_s["while.1 while"] == pytest.approx(2000e-9)
    assert s.busy_s == pytest.approx(2700e-9)
    assert dict(s.idle_gaps)["fill"] == pytest.approx(100e-9)
    names, _ = scopes.parse_hlo(HLO)
    got = scopes.scope_seconds(s.op_s, names)
    want = {"stage/ip_rx/bytes/shift": 1250e-9, "obs/counters": 150e-9,
            "stage/udp_tx/bytes/csum": 100e-9, "unscoped": 100e-9}
    assert got.keys() == want.keys()
    for k, v in want.items():
        assert got[k] == pytest.approx(v), k
    assert scopes.device_scopes(got)[0] == (
        "stage/ip_rx", pytest.approx(1250e-9))


def _ctx(monkeypatch, hlo=HLO):
    monkeypatch.setattr(scopes, "program_hlo", lambda ctx: hlo)
    return {"trace": _trace(), "traced_rows": [np.array([8])] * 2,
            "traced_ok": [np.array([8])] * 2,
            "cfg": {"name": "udp_echo"},
            "_host_counters": {
                "ingress/fill": {"calls": 3.0, "seconds": 0.006,
                                 "frames": 2400.0},
                "compile/trace": {"calls": 2.0, "seconds": 1.5},
                "compile/lower": {"calls": 2.0, "seconds": 0.5},
                "compile/backend": {"calls": 2.0, "seconds": 4.0},
                "compile/backend/jit(run_stream)": {"calls": 1.0,
                                                    "seconds": 3.9}}}


@pytest.mark.parametrize("metric,want", [
    ("shift_share.rate", 100 * 1250 / 2000),
    ("shift_share.rpc", 100 * 1250 / 2000),
    ("csum_share.rate", 100 * 100 / 2000),
    ("csum_share.rpc", 100 * 100 / 2000),
    ("obs_share.rate", 100 * 150 / 2000),
    ("obs_share.rpc", 100 * 150 / 2000),
    ("fill_ns_per_frame.rate", 0.006 * 1e9 / 2400),
    ("compile_s", 6.0),
])
def test_new_metric_reads_its_value(metric, want, monkeypatch):
    assert harness.load_metric(metric).read(_ctx(monkeypatch)) == \
        pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "shift_share.rate", "csum_share.rpc", "obs_share.rate",
    "fill_ns_per_frame.rate", "compile_s"])
def test_new_metric_finds_nothing_in_a_program_without_it(metric,
                                                          monkeypatch):
    """A program with no scopes and no host counters (one that predates
    them): nothing to read, and no error."""
    ctx = _ctx(monkeypatch, hlo=HLO.replace("/stage/", "/").replace(
        "/obs/", "/").replace("/bytes/", "/"))
    ctx["_host_counters"] = None
    assert harness.load_metric(metric).read(ctx) is None


def test_existing_metrics_read_the_same_trace_as_before(monkeypatch):
    ctx = _ctx(monkeypatch)
    assert harness.load_metric("device_ns_per_frame.rate").read(ctx) == \
        pytest.approx(2000 / 16)
    assert harness.load_metric("device_ms_per_window.rpc").read(ctx) == \
        pytest.approx(1000e-6)
    assert harness.load_metric("idle_share.rate").read(ctx) == \
        pytest.approx(100 * (1 - 2700 / 3000))


def test_program_hlo_is_the_runs_program_with_its_scopes():
    """Built again from the run's configuration, the stream program
    compiles to the same instructions each time, and every stage of the
    echo stack maps to its scope."""
    cfg, _ = harness.load_config("udp_echo")
    cfg.update(cpu_run.SMALL["echo.64B"])
    first, second = (scopes.parse_hlo(scopes.program_hlo(
        {"cfg": cfg}))[0] for _ in range(2))
    assert first == second
    stages = {scopes.outer(p) for p in first.values()}
    for node in ("eth_rx", "ip_rx", "udp_rx", "echo", "udp_tx", "ip_tx",
                 "eth_tx", "mgmt"):
        assert f"stage/{node}" in stages
    assert {"obs/counters", "obs/recorder"} <= stages
    paths = set(first.values())
    assert any(scopes.inner_of(p) == "bytes/shift" for p in paths)
    assert any(scopes.inner_of(p) == "bytes/csum" for p in paths)


def test_host_counters_of_a_run_hold_set_up_and_the_window():
    """On a run on the CPU the program's ``ingress/fill`` counts one fill
    per window, set-up's warm-up window included, and no compile follows
    the window's start."""
    import jax.monitoring

    from repro.launch import compile_cache
    from repro.obs import host
    from jax._src import monitoring
    compile_cache.watch_compiles()
    host.reset()
    seen = []

    def note(event, duration, **kw):
        seen.append((event, time.perf_counter()))

    jax.monitoring.register_event_duration_secs_listener(note)
    keep = {}
    try:
        out = cpu_run.run("echo.64B", keep=keep)
    finally:
        monitoring._event_duration_secs_listeners.remove(note)
    cpu_run.assert_sound(out)
    fill = host.counters()["ingress/fill"]
    assert fill["calls"] == keep["windows"] + 1
    setup_frames = fill["frames"] - keep["frames"]
    assert 0 < setup_frames <= 2 * 16
    assert not [e for e, t in seen
                if e.endswith("backend_compile_duration")
                and t > keep["t0"]]
    assert host.counters()["compile/backend"]["calls"] >= 1


def test_program_hlo_reuses_the_step_that_ran():
    """Where the run's system is at hand (``run_cell``'s frame holds it
    as ``system``), its own step is lowered again: the executable that
    ran comes back from JAX's caches, with no compile."""
    from repro.launch import compile_cache
    from repro.obs import host
    compile_cache.watch_compiles()
    cfg, mod = harness.load_config("udp_echo")
    cfg.update(cpu_run.SMALL["echo.64B"])
    system = mod.build(cfg)
    p, l = system.put(system.new_arena())
    system.step(system.init_state(), p, l)
    assert scopes.run_system() is system
    before = host.counters()["compile/backend"]["calls"]
    names, _ = scopes.parse_hlo(scopes.program_hlo({"cfg": cfg}))
    assert host.counters()["compile/backend"]["calls"] == before
    assert "stage/echo" in {scopes.outer(p) for p in names.values()}
