"""Percentile and rate arithmetic, through the metric readers."""

import types

import numpy as np
import pytest

from harness import spec, stats


def ctx_of(rec, t0, t1):
    return types.SimpleNamespace(
        rec=rec, t0=t0, t1=t1, due_in_window=stats.in_window(rec, t0, t1))


def open_loop(n=1000, seconds=10.0, service=0.002):
    due = np.linspace(0.0, seconds, n, endpoint=False)
    return {"due": due, "sent": due.copy(), "done": due + service,
            "status": np.full(n, 200)}


def test_percentiles_of_a_steady_server():
    rec = open_loop()
    ctx = ctx_of(rec, 0.0, 10.0)
    assert spec.reader("search_p50_ms")(ctx) == pytest.approx(2.0)
    assert spec.reader("search_p95_ms")(ctx) == pytest.approx(2.0)
    assert spec.reader("search_qps")(ctx) == pytest.approx(100.0)


def test_a_generator_stall_shows_in_the_p95():
    # the generator stalls for 0.6 s at t=5: requests due then are sent late
    # and served fast; timed from their send they look fine, from their due
    # time they are up to 600 ms late -- 6% of the window's requests
    rec = open_loop()
    stalled = (rec["due"] >= 5.0) & (rec["due"] < 5.6)
    rec["sent"][stalled] = 5.6
    rec["done"][stalled] = 5.6 + 0.002
    ctx = ctx_of(rec, 0.0, 10.0)
    p95 = spec.reader("search_p95_ms")(ctx)
    assert 100.0 < p95 < 600.0
    from_send = (rec["done"] - rec["sent"]) * 1e3
    assert np.percentile(from_send, 95) == pytest.approx(2.0)
    assert spec.reader("search_p50_ms")(ctx) == pytest.approx(2.0)
    assert stats.percentile(stats.lateness_ms(rec), 99) > 100.0


def test_failed_requests_miss_every_limit():
    rec = open_loop(n=100)
    rec["status"][:10] = 0
    lat = stats.latency_ms(rec)
    assert (lat[:10] == stats.FAILED_MS).all()
    assert spec.reader("search_p95_ms")(ctx_of(rec, 0.0, 10.0)) >= 1e8
    # a closed loop counts only successful replies inside the window
    assert stats.completed_rate(rec, 0.0, 10.0) == pytest.approx(9.0)


def test_window_bounds():
    rec = open_loop(n=100)
    assert stats.in_window(rec, 2.0, 4.0).sum() == 20
    assert stats.completed_rate(rec, 2.0, 4.0) == pytest.approx(10.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
