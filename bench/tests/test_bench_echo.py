"""udp_echo on the CPU: a small window through the stack matches the
reference, and every fault the cell can have, and the control, make the
run not correct."""
import pytest

from bench.tests.cpu_run import assert_sound, run


@pytest.mark.parametrize("cell", ["echo.64B", "echo.imix"])
def test_small_window_matches_reference(cell):
    out = run(cell)
    assert_sound(out)
    assert set(out["metrics"]) >= {"setup_s"}
    assert out["checks"]["drop_gap"]["value"] == 0


@pytest.mark.parametrize("fault,number", [
    ("udp_csum_zero", "reply_mismatch"),
    ("answer_altered", "reply_mismatch"),
    ("half_batch", "reply_missing"),
    ("state_unchanged", "served_gap"),
])
def test_fault_is_not_correct(fault, number):
    out = run("echo.64B", fault)
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]
