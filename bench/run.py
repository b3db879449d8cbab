#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run
builds the cell's deployment through the program's own API, makes the
client's traffic from ``--seed``, warms up the one window shape the cell
uses (set-up), then serves the traffic for ``--seconds`` and checks every
reply against the plain reference.  ``--trace 1`` records a profiler
trace of the window and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``: each number compared with the
reference beside its limit.  The same numbers close standard error.
Without a TPU, with fewer chips than the cell asks for, or outside a
checkout of the repository, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def prepare(workload: str):
    """Check that this is a checkout with a TPU holding the chips that
    ``workload`` asks for, and turn on the compile cache.  Returns
    ``(BENCHMARK.json, None)``, or ``(None, exit code)`` after saying
    why not on standard error."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no program under {SRC}; run from a checkout of "
              f"the repository", file=sys.stderr)
        return None, 2
    sys.path[:0] = [SRC, ROOT]
    from bench import harness
    bench = harness.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        print(f"bench: no workload {workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return None, 2
    chips = int(cells[workload]["chips"])

    import jax
    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    print(f"device {json.dumps(found)}", file=sys.stderr, flush=True)
    if found["platform"] != "tpu":
        print(f"bench: needs a TPU, JAX found {json.dumps(found)}",
              file=sys.stderr)
        return None, 1
    if len(devs) < chips:
        print(f"bench: {workload} needs {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None, 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache {enable_compile_cache()}", file=sys.stderr,
          flush=True)
    return bench, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, code = prepare(args.workload)
    if bench is None:
        return code
    from bench import harness
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, bench=bench)
    print(f"correct {out['correct']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
