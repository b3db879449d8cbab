"""A run of a cell on the CPU at a size a test can hold: the harness
below its look for a chip, with a small arena and one shard."""
from __future__ import annotations

import io
import time

from bench import faults, harness

# each configuration with a mix, as cells of their own: the tests hold the
# configurations to their references whichever cells BENCHMARK.json lists
CELLS = {
    "echo.64B": ("udp_echo", "64B"),
    "echo.imix": ("udp_echo", "imix"),
    "echo4.64B": ("udp_echo_rss4", "64B"),
    "rs.open": ("rs_encode", "open"),
}
SMALL = {
    "echo.64B": {"n_batches": 2, "batch": 16},
    "echo.imix": {"n_batches": 2, "batch": 16},
    "echo4.64B": {"n_batches": 2, "batch": 16, "shards": 1},
    "rs.open": {"n_batches": 2, "batch": 8},
}
SMALL_MIX = {"rs.open": {"rate_per_s": 400.0}}


def run(cell: str, fault: str = None, seed: int = 2**33 + 17,
        seconds: float = 0.05, keep: dict = None) -> dict:
    bench = dict(harness.load_benchmark(), workloads=[
        {"name": n, "config": c, "traffic": t, "chips": 1}
        for n, (c, t) in CELLS.items()])
    return harness.run_cell(
        cell, seed, seconds, False, time.perf_counter(), bench=bench,
        overrides=SMALL[cell], mix_overrides=SMALL_MIX.get(cell),
        fault=faults.FAULTS[fault] if fault else None, keep=keep,
        log=io.StringIO())


def assert_sound(out: dict):
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(c["value"] == 0 for c in out["checks"].values())
