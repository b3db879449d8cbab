"""Set-up: process start to the first timed window, compilation (or the
read from the compile cache), traffic and warm-up window included."""


def read(ctx):
    return ctx["setup_s"]
