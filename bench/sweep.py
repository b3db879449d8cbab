#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: serve the
cell's mix at each offered rate and report whether the backlog grows.

    python3 bench/sweep.py --workload rs.open --rates 1000,2000,4000 \\
        --seconds <s> --seed <n>

For each rate one JSON line: the latency percentiles, the requests still
unanswered when the window closed (the backlog), and the median latency
of the second and of the last quarter of the arrivals.  A backlog that
grows shows as a last quarter far slower than the second.  The knee is
the highest rate at which it does not; the cell's mix then offers a
fixed rate below it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.run import prepare  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    bench, code = prepare(args.workload)
    if bench is None:
        return code
    import numpy as np
    from bench import harness
    for rate in (float(r) for r in args.rates.split(",")):
        ctx = {}
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               False, time.perf_counter(), bench=bench,
                               mix_overrides={"rate_per_s": rate}, keep=ctx)
        lat = ctx["latency_s"]
        done_at = ctx["due"][:len(lat)] + lat
        q = len(lat) // 4
        served = np.diff(ctx["reply_times"]) * 1e3
        print(json.dumps({
            "rate_per_s": rate, "correct": out["correct"],
            "requests": len(lat), "windows": ctx["windows"],
            "backlog_at_close": int((done_at > args.seconds).sum()),
            "p50_ms_q2": float(np.median(lat[q:2 * q])) * 1e3,
            "p50_ms_q4": float(np.median(lat[3 * q:])) * 1e3,
            "reply_gap_ms": [float(np.percentile(served, p))
                             for p in (50, 90, 99, 100)],
            "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
