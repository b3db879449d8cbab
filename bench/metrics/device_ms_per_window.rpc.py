"""Executor and tiles: the device time of one window's program run, the
mean over the whole runs in the trace, in ms."""
from bench.readers import traced_runs


def read(ctx):
    runs = traced_runs(ctx)
    if not runs:
        return None
    return sum(sec for _, _, sec in runs) * 1e3 / len(runs)
