"""Median time a search spends outside the engine: the client's
send-to-reply time minus the engine's submit-to-deliver ``latency_ms`` of
the same response (HTTP framing, JSON parse and serialisation, executor
hand-off, and the client's own read), over the window's successful
requests."""

import numpy as np


def read(ctx):
    r = ctx.rec
    m = ctx.due_in_window & (r["status"] == 200)
    v = (r["done"][m] - r["sent"][m]) * 1e3 - r["server_latency_ms"][m]
    return float(np.median(v)) if v.size else None
