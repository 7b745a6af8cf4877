"""Seconds from the start of the process to the start of the window:
JAX start-up, corpus generation and loading, index build, compilation or
the compile cache, and the load generators' preparation (host clock)."""


def read(ctx):
    return ctx.setup_s
