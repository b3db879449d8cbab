"""Executor and tiles: device time of the ops under the program's
``bytes/csum`` scope (the whole-width checksums of ``net/bytesops.py``)
over the device time of the stream program's whole runs in the trace,
both summed over the chips, in % (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.share(ctx, inner=scopes.CSUM)
