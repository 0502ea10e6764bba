"""Compile: seconds of the warm-up calls before the window, on the host
clock; compilation or loading from the persistent cache."""


def read(ctx):
    return ctx.get("warmup_s")
