"""The RS(8,2) Pallas kernel's share of its roofline, in %.

Least time: the requests encoded in the whole program runs of the trace
(``bench/readers.py``), each reading 4096
data bytes and writing 1024 parity bytes, at the chip's HBM peak
(``bench/peaks.py``).  The bytes are what the requests need, so a padded
or fused kernel is held to the same work.  It is a memory bound: the
published compute peaks are the MXU's and do not bound this VPU kernel.

Kernel time: the summed device time of the kernel's op events in those
runs.  In the
trace the kernel is a ``custom-call`` to ``tpu_custom_call`` named after
the conditional branch around it (the ``pallas_call`` has no name of its
own); it is the only Pallas kernel on this path.  Where an op names the
RS kernel, only those count.
"""
from bench.peaks import peaks
from bench.readers import traced_runs

TARGET = "tpu_custom_call"
NAMES = ("rs_encode", "_rs_kernel")
BYTES_PER_REQUEST = 4096 + 1024


def kernel_s(trace) -> float:
    calls = {k: v for k, v in trace.op_s.items() if TARGET in k}
    named = {k: v for k, v in calls.items() if any(n in k for n in NAMES)}
    return sum((named or calls).values())


def read(ctx):
    runs = traced_runs(ctx)
    encoded = sum(int(ctx["traced_ok"][r][d]) for d, r, _ in runs)
    if encoded == 0:
        return None
    spent = kernel_s(ctx["trace"])
    if spent <= 0:
        return None
    least_s = (encoded * BYTES_PER_REQUEST
               / peaks(ctx["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least_s / spent
