"""95th percentile of search latency over every request due in the window,
from when it was due to its reply (host clock); a failed request counts as
missing every limit."""

from harness import stats


def read(ctx):
    return stats.percentile(stats.latency_ms(ctx.rec)[ctx.due_in_window], 95)
