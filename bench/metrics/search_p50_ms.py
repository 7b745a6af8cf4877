"""Median search latency over every request due in the window, from when it
was due to its reply (host clock)."""

from harness import stats


def read(ctx):
    return stats.percentile(stats.latency_ms(ctx.rec)[ctx.due_in_window], 50)
