"""The reduction from a profiler trace to busy, idle and kernel time."""

import json
import os

import numpy as np
import pytest

from harness import trace as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, name, start, dur, line=tr.OPS_LINE, **stats):
    return tr.Event(plane, line, name, float(start), float(dur),
                    tuple(stats.items()))


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    events = [ev(DEV, "fusion.1", 100, 200),        # 100-300
              ev(DEV, "fusion.2", 250, 100),        # 250-350, overlaps
              ev(DEV, "custom-call.3", 600, 100, long_name="ivf_scan"),
              ev(DEV, "late", 950, 200),            # clipped at 1000
              ev(HOST, "bench.dispatch", 50, 700, line="python"),
              ev(HOST, "bench.execute_batch", 0, 900, line="python")]
    s = tr.summarize(events, 1000.0)
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx((250 + 100 + 50) / 1e9)
    assert s.window_s == pytest.approx(1e-6)
    assert s.op_time("ivf_scan") == (1, pytest.approx(100 / 1e9))
    # gaps: 0-100 and 350-600 inside the dispatch span, 700-950 past it
    # but inside execute_batch; named by the innermost covering span
    assert s.gaps == [("bench.dispatch", pytest.approx(250e-9)),
                      ("bench.execute_batch", pytest.approx(250e-9)),
                      ("bench.dispatch", pytest.approx(100e-9))]
    assert s.top_ops(2)[0] == ("fusion.1", pytest.approx(200e-9))


def test_two_devices_average():
    events = [ev(DEV, "a", 0, 500), ev("/device:TPU:1", "a", 0, 100)]
    s = tr.summarize(events, 1000.0)
    assert s.busy_s == pytest.approx(300e-9)
    assert s.op_time("a") == (1, pytest.approx(300e-9))


def recorded():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "ivf_trace_excerpt.json")
    with open(path) as f:
        d = json.load(f)
    return d["window_ns"], [
        tr.Event(d["planes"][p], d["lines"][ln], name, start, dur)
        for p, ln, name, start, dur in d["events"]]


def test_a_recorded_tpu_trace():
    window, events = recorded()
    s = tr.summarize(events, window)
    assert s.n_devices == 1
    # busy time against a plain timeline at 10 ns resolution
    busy = np.zeros(int(window / 10) + 1, bool)
    for e in events:
        if e.plane.startswith(tr.DEVICE_PREFIX):
            busy[max(int(e.start_ns / 10), 0):int(e.end_ns / 10)] = True
    assert s.busy_s == pytest.approx(busy.sum() * 10 / 1e9, rel=1e-3)
    assert sum(g for _, g in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    # the IVF kernel: one Pallas custom call per dispatch, found by the
    # roofline reader's pattern and nothing else
    from harness import spec
    pattern = spec._module(os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "metrics",
        "ivf_scan_roofline.py"), "roofline").PATTERN
    calls, seconds = s.op_time(*pattern)
    kernel = [e for e in events if e.name.startswith("%_ivf_scan_call")
              and "custom-call(" in e.name]
    assert calls == len(kernel) >= 1
    assert seconds == pytest.approx(sum(e.dur_ns for e in kernel) / 1e9)
    assert {n for n, _ in s.gaps} <= {"no host span", "bench.http_search",
                                      "bench.execute_batch", "bench.dispatch"}
