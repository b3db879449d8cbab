# Test tiers
#
#   make test-fast   tier-1 verify loop: everything except @slow
#                    (distributed subprocess suite, per-arch model smokes,
#                    trainer loops, big kernel sweeps) — about a minute
#   make test        the full suite (what CI / the PR gate runs)
#   make bench       the paper-benchmark battery

PY ?= python
# src for the repro package, the repo root for the benchmarks package
PYTHONPATH := src:.$(if $(PYTHONPATH),:$(PYTHONPATH),)
export PYTHONPATH

.PHONY: test-fast test bench bench-mgmt bench-tcp-loss bench-stream \
        bench-rpc-tail bench-shard lint-reasons

test-fast:
	$(PY) -m pytest -q -m "not slow"

test:
	$(PY) -m pytest -q

# static drop-reason coverage: every registered tile that can squash
# `pred` must attribute a reason code (also run as a test in
# tests/test_export.py)
lint-reasons:
	$(PY) -m repro.obs.lint

bench:
	$(PY) benchmarks/run.py

# management-plane contention regression check (paper: control traffic
# never contends with the dataplane)
bench-mgmt:
	$(PY) benchmarks/bench_mgmt.py

# loss-tolerant transport gate: goodput + p99 recovery latency through
# the netem link at 0.1% / 1% loss (fails on stall or < 20% goodput)
bench-tcp-loss:
	$(PY) benchmarks/bench_tcp_loss.py

# streaming-executor gate: streamed UDP echo pps must be >= 3x the
# per-batch baseline; writes BENCH_stream.json (the perf trajectory)
bench-stream:
	$(PY) benchmarks/bench_stream.py

# direct-attached serving gate: LM request p99 through the compiled stack
# (lm_serve tile inside run_stream) must be <= 0.5x the host-mediated
# baseline; APPENDS a trajectory entry to BENCH_rpc_tail.json
bench-rpc-tail:
	$(PY) benchmarks/bench_rpc_tail.py

# sharded-dataplane gate: RSS-replicated stack under shard_map on a
# host-simulated 8-device mesh — certified (no collectives, no host
# callbacks, bit-identical egress) projected aggregate must be >= 4x the
# single-device baseline; APPENDS to BENCH_shard.json
bench-shard:
	$(PY) benchmarks/bench_shard.py
