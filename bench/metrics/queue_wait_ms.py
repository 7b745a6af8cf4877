"""Median ``spans.queue_ms`` (submit to dispatch: the driver's batching
wait and the wait for the engine) over the window's successful requests."""

import numpy as np


def read(ctx):
    r = ctx.rec
    v = r["queue_ms"][ctx.due_in_window & (r["status"] == 200)]
    return float(np.median(v)) if v.size else None
