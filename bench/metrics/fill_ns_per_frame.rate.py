"""Host ingress: the program's own ``ingress/fill`` span
(``FrameArena.fill``, ``ShardedFrameArena.fill_rss``), its seconds over
the frames it filled, in ns per frame.  The counter holds every fill of
the run: the window's and set-up's one warm-up window."""
from bench import scopes


def read(ctx):
    c = (scopes.host_counters(ctx) or {}).get("ingress/fill")
    if not c or not c.get("frames"):
        return None
    return c["seconds"] * 1e9 / c["frames"]
