"""rs_encode on the CPU (the Pallas kernel interpreted): a small
open-loop window matches the RS reference; the control and the faults
make the run not correct."""
import numpy as np
import pytest

from bench.tests.cpu_run import assert_sound, run


def test_small_window_matches_reference():
    out = run("rs.open")
    assert_sound(out)
    assert out["metrics"]["rpc_p50_ms"]["value"] <= \
        out["metrics"]["rpc_p95_ms"]["value"]


@pytest.mark.parametrize("fault,number", [
    ("udp_csum_zero", "reply_mismatch"),
    ("answer_altered", "reply_mismatch"),
    ("half_batch", "reply_missing"),
    ("state_unchanged", "served_gap"),
])
def test_fault_is_not_correct(fault, number):
    out = run("rs.open", fault)
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]


def test_open_loop_latency_counts_every_request():
    ctx = {}
    run("rs.open", seed=5, keep=ctx)
    assert len(ctx["latency_s"]) == len(ctx["due"]) == 20
    assert np.all(np.isfinite(ctx["latency_s"]))
