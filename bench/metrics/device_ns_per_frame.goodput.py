"""Executor and tiles: device time of the stream program's whole runs in
the trace, summed over the chips, over the frames they served, in ns."""
from bench.readers import device_ns_per_frame as read  # noqa: F401
