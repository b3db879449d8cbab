"""udp_echo_rss4 on the CPU as a one-shard ShardedStream: a small
window through fill_rss and the sharded program matches the reference;
the control and the faults make the run not correct."""
import pytest

from bench.tests.cpu_run import assert_sound, run


def test_small_window_matches_reference():
    assert_sound(run("echo4.64B"))


@pytest.mark.parametrize("fault,number", [
    ("udp_csum_zero", "reply_mismatch"),
    ("answer_altered", "reply_mismatch"),
    ("half_batch", "reply_missing"),
    ("state_unchanged", "served_gap"),
])
def test_fault_is_not_correct(fault, number):
    out = run("echo4.64B", fault)
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]
