"""Successful searches whose reply arrived inside the window, over the
window's seconds (host clock)."""

from harness import stats


def read(ctx):
    return stats.completed_rate(ctx.rec, ctx.t0, ctx.t1)
