"""Executor and tiles: device time of the ops under the program's
``obs/*`` scopes (the executor's counters, drop table, flight recorder
and histograms, series, postcards and watchdog) over the device time of
the stream program's whole runs in the trace, both summed over the
chips, in % (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, block="obs/")
