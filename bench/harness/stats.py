"""Percentile and rate arithmetic over the client's request records.

Times are seconds on the host's monotonic clock, shared by the server and
the load generator processes.  An open-loop request is timed from when it
was due, so a stall that delays later sends shows in their latency; a
request that failed or never came back counts as `FAILED_MS`, past every
limit.
"""

from __future__ import annotations

import numpy as np

FAILED_MS = 1e9


def percentile(values, p: float) -> float:
    """The p-th percentile, linear between order statistics."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, p))


def latency_ms(rec) -> np.ndarray:
    """Per-request latency from due to reply, FAILED_MS where it failed."""
    ok = rec["status"] == 200
    return np.where(ok, (rec["done"] - rec["due"]) * 1e3, FAILED_MS)


def in_window(rec, t0: float, t1: float) -> np.ndarray:
    """Requests due inside the window [t0, t1)."""
    return (rec["due"] >= t0) & (rec["due"] < t1)


def completed_rate(rec, t0: float, t1: float) -> float:
    """Successful replies that arrived inside [t0, t1], per second."""
    ok = (rec["status"] == 200) & (rec["done"] >= t0) & (rec["done"] <= t1)
    return float(np.count_nonzero(ok)) / (t1 - t0)


def lateness_ms(rec) -> np.ndarray:
    """How late the generator sent each request (send minus due)."""
    return (rec["sent"] - rec["due"]) * 1e3
