#!/usr/bin/env python3
"""Readings for the limits: run a cell on several seeds in one process,
as the program is (``--fault none``) or with a fault planted under the
timed path (``bench/faults.py``; ``udp_csum_zero`` is the control).

    python3 bench/control.py --workload <cell> --fault <name|none> \\
        --seeds 1,2,3 --seconds <s>

Prints one JSON line per seed with ``correct`` and every number compared.
The benchmark's own runs never plant a fault.
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
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench, code = prepare(args.workload)
    if bench is None:
        return code
    from bench import faults, harness
    fault = None if args.fault == "none" else faults.FAULTS[args.fault]
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = harness.run_cell(args.workload, seed, args.seconds, False, t,
                               bench=bench, fault=fault)
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": out["metrics"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
