"""A configuration's own ``judge`` in the harness's check, on the CPU: a
judge that repeats the byte comparison reads as the default one, a judge
sees every window in dispatch order with its arrival numbers, a judge's
own checks count toward ``correct``, the faults still fail a judging
configuration, and a cell that is only in ``BENCHMARK.json`` runs at its
configuration's ``CPU_SMALL``."""
from __future__ import annotations

import io
import json
import os
import types

import numpy as np
import pytest

from bench import harness
from bench.tests.cpu_run import CELLS, assert_sound, run


def repeat_bytes(expected):
    """The byte comparison written out row by row: a reply is wrong where
    its length or one of its bytes differs from what ``expected``
    builds."""
    def judge(cfg, pool, windows, states, cap):
        exp, exp_len, rlen = expected(cfg, pool, cap)
        wrong, body_len = [], []
        for win in windows:
            S, rows = win.rows.shape
            tx_len = win.got["tx_len"].reshape(S, rows)
            payload = win.got["tx_payload"].reshape(S, rows, cap)
            w = np.zeros((S, rows), bool)
            b = np.zeros((S, rows), np.int64)
            for s in range(S):
                for r in range(rows):
                    i = win.rows[s, r]
                    if i < 0:
                        continue
                    n = int(exp_len[i])
                    w[s, r] = (int(tx_len[s, r]) != n
                               or payload[s, r, :n].tobytes()
                               != exp[i, :n].tobytes())
                    b[s, r] = rlen[i]
            wrong.append(w)
            body_len.append(b)
        return {"wrong": wrong, "body_len": body_len}
    return judge


def with_judge(monkeypatch, make_judge):
    """Every configuration loaded from now on judges with
    ``make_judge(module)`` and has no ``expected`` left to fall back on."""
    load = harness.load_config

    def load_config(name):
        cfg, mod = load(name)
        mod = types.SimpleNamespace(**vars(mod))
        mod.judge = make_judge(mod)
        del mod.expected
        return cfg, mod
    monkeypatch.setattr(harness, "load_config", load_config)


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_judge_repeating_the_bytes_reads_as_the_default(cell, monkeypatch):
    """On the same replies, the default judge and one written to repeat it
    give the same checks and counts."""
    verdicts = []
    check = harness.check

    def both(cfg, cfgmod, *args):
        default = check(cfg, cfgmod, *args)
        judging = types.SimpleNamespace(
            **vars(cfgmod), judge=repeat_bytes(cfgmod.expected))
        judged = check(cfg, judging, *args)
        verdicts.append((default, judged))
        return dict(judged, checks=dict(judged["checks"]))
    monkeypatch.setattr(harness, "check", both)
    out = run(cell)
    assert_sound(out)
    (default, judged), = verdicts
    assert judged["checks"] == default["checks"]
    assert judged["correct"] == default["correct"] is True
    for k in ("attempted", "failed", "replies_ok", "reply_body_bytes"):
        assert judged["window"][k] == default["window"][k], k
    assert out["attempted"] == default["window"]["attempted"] > 0
    lat = default["window"]["latency_s"]
    if lat is not None:
        assert np.array_equal(judged["window"]["latency_s"], lat)


@pytest.mark.parametrize("cell", ["rs.open", "echo.64B"])
def test_the_judge_sees_every_window_in_dispatch_order(cell, monkeypatch):
    """Set-up's window first, then the measured ones; in an open loop
    their arrival numbers cover every due arrival once, in order."""
    seen = {}

    def recording(mod):
        default = harness.expected_judge(mod.expected)

        def judge(cfg, pool, windows, states, cap):
            seen.update(pool=pool, windows=list(windows), states=states)
            return default(cfg, pool, windows, states, cap)
        return judge
    with_judge(monkeypatch, recording)
    ctx = {}
    assert_sound(run(cell, keep=ctx))
    pool, windows = seen["pool"], seen["windows"]
    assert ctx["pool"] is pool
    assert len(seen["states"]) == 1
    first, rest = windows[0], windows[1:]
    assert not first.measured and rest and all(w.measured for w in rest)
    rows = first.rows.shape[1]
    for w in windows:
        assert set(w.got) >= {"tx_payload", "tx_len", "alive", "served"}
    if pool.loop == "open":
        assert np.array_equal(first.arrivals, np.arange(rows))
        arrivals = np.concatenate([w.arrivals for w in rest])
        assert np.array_equal(arrivals, np.arange(len(pool.due)))
        for w in windows:
            n = len(w.arrivals)
            assert np.array_equal(w.rows[0, :n],
                                  w.arrivals % len(pool.frames))
            assert np.all(w.rows[0, n:] == -1)
    else:
        assert all(w.arrivals is None for w in windows)
        for k, w in enumerate(windows):
            assert np.array_equal(w.rows,
                                  pool.windows[k % len(pool.windows)])


@pytest.mark.parametrize("value,correct", [(0.5, False), (0.125, True)])
def test_a_judges_own_check_counts_toward_correct(value, correct,
                                                  monkeypatch):
    def extra(mod):
        default = harness.expected_judge(mod.expected)

        def judge(*args):
            return dict(default(*args), checks={
                "logit_gap": {"value": value, "limit": 0.25}})
        return judge
    with_judge(monkeypatch, extra)
    out = run("echo.64B")
    assert out["correct"] is correct
    assert out["checks"]["logit_gap"] == {"value": value, "limit": 0.25}
    assert out["checks"]["reply_mismatch"]["value"] == 0


def test_a_judge_may_not_take_a_built_in_check(monkeypatch):
    def clash(mod):
        default = harness.expected_judge(mod.expected)
        return lambda *args: dict(default(*args), checks={
            "served_gap": {"value": 0, "limit": 0}})
    with_judge(monkeypatch, clash)
    with pytest.raises(ValueError, match="served_gap"):
        run("echo.64B")


# ---- a configuration that is only in BENCHMARK.json, and judges itself

JUDGED = '''"""UDP echo, judging its replies with a judge of its own."""
from bench.configs.udp_echo import (build, expected as _expected,
                                    reply_cap, request_body_len, requests,
                                    served)
from bench.tests.test_bench_judge import repeat_bytes

CPU_SMALL = {"n_batches": 2, "batch": 32}
CPU_SMALL_MIX = {"pool_windows": 3}
judge = repeat_bytes(_expected)
'''


@pytest.fixture
def judged_bench(tmp_path, monkeypatch):
    """BENCHMARK.json with one more cell, ``judged.64B``, whose
    configuration lives in a directory of its own."""
    with open(os.path.join(harness.CONFIGS, "udp_echo.json")) as f:
        cfg = dict(json.load(f), name="echo_judged")
    (tmp_path / "echo_judged.json").write_text(json.dumps(cfg))
    (tmp_path / "echo_judged.py").write_text(JUDGED)
    monkeypatch.setattr(harness, "CONFIGS", str(tmp_path))
    bench = harness.load_benchmark()
    return dict(bench, workloads=bench["workloads"] + [
        {"name": "judged.64B", "config": "echo_judged", "traffic": "64B",
         "chips": 1, "why": "a test's own cell"}])


def test_a_cell_only_in_benchmark_json_runs_at_its_cpu_small(judged_bench):
    ctx, log = {}, io.StringIO()
    out = run("judged.64B", bench=judged_bench, keep=ctx, log=log)
    assert_sound(out)
    assert (ctx["cfg"]["n_batches"], ctx["cfg"]["batch"]) == (2, 32)
    assert ctx["mix"]["pool_windows"] == 3
    assert len(ctx["pool"].windows) == 3
    assert ctx["traced_windows"] == []          # an untraced run
    assert "judge_s " in log.getvalue()


@pytest.mark.parametrize("fault,number", [
    ("answer_altered", "reply_mismatch"),
    ("half_batch", "reply_missing"),
    ("state_unchanged", "served_gap"),
])
def test_fault_is_not_correct_under_a_judge(fault, number, judged_bench):
    out = run("judged.64B", fault, bench=judged_bench)
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]
