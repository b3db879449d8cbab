"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader gets the run's context (``harness.run_cell`` builds it) and
returns a number, or None where it finds nothing to read; the harness
then leaves the metric out of the result line.

Device time per unit of work comes from the stream program's runs that
started and ended inside the traced span (``Summary.runs``): run r of a
device served the traced window r (``ctx["traced_rows"][r]``, the rows
each shard held; ``ctx["traced_ok"][r]``, the correct replies).  A
metric that counts a configuration's own work in a window (tokens,
bytes) finds it in ``ctx["pool"]``, the run's requests, and
``ctx["traced_windows"][r]``: window r's ``rows`` (request indices, -1
for an empty row) and ``good`` (which rows got a correct reply).
"""
from __future__ import annotations

import math

import numpy as np


def percentile_ms(ctx, q: float):
    """Nearest-rank percentile of every request's latency in the window,
    in ms.  A request that got no reply, or a wrong one, counts as
    missing every limit: as the whole time the run waited."""
    lat = ctx.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    lat = np.sort(np.where(np.isfinite(lat), lat,
                           ctx["window_s"] + ctx["give_up_s"]))
    rank = max(1, math.ceil(q / 100.0 * len(lat)))
    return float(lat[rank - 1]) * 1e3


def traced_runs(ctx):
    """(device, window, seconds) of each whole program run traced."""
    tr = ctx.get("trace")
    if tr is None:
        return []
    n = len(ctx["traced_rows"])
    return [(d, r, sec) for d, runs in enumerate(tr.runs)
            for r, sec in runs if r < n]


def device_ns_per_frame(ctx):
    """Device time of the whole runs, summed over the chips, over the
    frames those runs served."""
    runs = traced_runs(ctx)
    frames = sum(int(ctx["traced_rows"][r][d]) for d, r, _ in runs)
    if frames == 0:
        return None
    return sum(sec for _, _, sec in runs) * 1e9 / frames


def idle_share(ctx):
    """1 - busy / traced span, the mean over the chips, in %."""
    tr = ctx.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
