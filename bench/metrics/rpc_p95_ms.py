"""95th percentile (nearest rank) of the latency over every request due
in the window, from its scheduled arrival to its reply on the host."""
from bench.readers import percentile_ms


def read(ctx):
    return percentile_ms(ctx, 95)
