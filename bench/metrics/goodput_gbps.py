"""Reply body bytes of correct replies in the measured window, times 8,
over its length, in Gbit/s.  Headers and the deliberately broken frames
do not count."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return ctx["reply_body_bytes"] * 8 / ctx["window_s"] / 1e9
