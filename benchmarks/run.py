"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    from benchmarks import (bench_flexibility, bench_lm, bench_mgmt,
                            bench_migration, bench_obs, bench_rs,
                            bench_shard, bench_stream, bench_tcp,
                            bench_tcp_loss, bench_udp_echo, bench_vr,
                            bench_resources)
    print("name,us_per_call,derived")
    failures = 0
    for mod in (bench_flexibility, bench_udp_echo, bench_stream, bench_tcp,
                bench_tcp_loss, bench_rs, bench_vr, bench_migration,
                bench_mgmt, bench_obs, bench_shard, bench_resources,
                bench_lm):
        try:
            mod.run()
        except Exception:
            failures += 1
            print(f"{mod.__name__},0,FAILED", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    enable_compile_cache()
    main()
