"""Device idle share of the traced window: 1 - busy / window, in %,
the mean over the chips."""
from bench.readers import idle_share as read  # noqa: F401
