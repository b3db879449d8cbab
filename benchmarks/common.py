"""Benchmark helpers: timing, CSV emission, TPU projection.

Every benchmark prints ``name,us_per_call,derived`` CSV rows (derived
carries the paper-comparable figure: Gbps, KReq/s, LoC, ...).

CPU wall time is NOT the paper's metric — the derived column projects TPU
throughput from the compiled HLO's per-call byte traffic (hlo_walk) against
v5e HBM bandwidth, and latency from the NoC cost model.  Both the measured
and projected figures are reported.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax

from repro.launch import hlo_walk
from repro.launch.hlo_analysis import HBM_BW, ICI_BW, PEAK_FLOPS


def time_call(fn: Callable, *args, warmup: int = 2, iters: int = 5,
              carry: bool = False) -> float:
    """Median wall microseconds per call (CPU measurement).

    ``carry=True`` threads the first element of fn's return value back as
    the new first argument on every call — required when the first
    argument is donated (``jax.jit(..., donate_argnums=(0,))``): the old
    state's buffers die with each call, so re-passing them would fault."""
    args = list(args)

    def call():
        out = fn(*args)
        jax.block_until_ready(out)
        if carry:
            args[0] = out[0] if isinstance(out, tuple) else out

    for _ in range(warmup):
        call()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        call()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def hlo_traffic(fn: Callable, *args) -> hlo_walk.WalkResult:
    """Walk the compiled HLO of fn(*args) for per-call flops/bytes."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return hlo_walk.walk(text)


def tpu_projected_seconds(w: hlo_walk.WalkResult) -> float:
    """Roofline-projected per-call seconds on one v5e chip."""
    return max(w.flops / PEAK_FLOPS, w.hbm_bytes / HBM_BW,
               w.coll_link_bytes / ICI_BW)


def row(name: str, us: float, derived: str) -> str:
    line = f"{name},{us:.2f},{derived}"
    print(line)
    return line


def append_trajectory(path: str, entry: dict) -> None:
    """Append one timestamped result to a BENCH_*.json trajectory file.
    History is the point: every PR adds a point, nothing is overwritten.
    A pre-trajectory file (any other JSON shape) is preserved under a
    ``legacy`` key rather than discarded."""
    data = {"trajectory": []}
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        if isinstance(old, dict) and isinstance(old.get("trajectory"),
                                                list):
            data = old
        else:
            data["legacy"] = old
    data["trajectory"].append({"ts": time.time(), **entry})
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
