"""Correct replies in the measured window over its length, in millions
of frames per second (closed loop, so the system sets the pace)."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return ctx["replies_ok"] / ctx["window_s"] / 1e6
