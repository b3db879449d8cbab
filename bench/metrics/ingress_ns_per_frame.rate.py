"""Host ingress: the benchmark's own host spans around
``FrameArena.fill`` / ``fill_rss`` and ``jax.device_put`` (until the
arena is on the device), over the frames sent, in ns per frame."""


def read(ctx):
    if ctx["frames"] == 0:
        return None
    s = ctx["spans"]
    return (s.get("fill", 0.0) + s.get("put", 0.0)) * 1e9 / ctx["frames"]
