"""Reduction of a profiler trace to device busy time, kernel time and the
longest idle gaps.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Its planes
named ``/device:TPU:<n>`` hold the device's timeline; on the ``XLA Ops``
line each event is one operation that ran on the chip.  Planes named
``/host:CPU`` hold the host threads, where the harness's own
``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``) mark
which layer the host was in.  Event times are nanoseconds from the start of
the traced session.

Busy time is the union of the operation intervals on a device inside the
window, averaged over the devices; idle time is the rest of the window.
Each idle gap is named by the innermost ``bench.`` span that covers its
midpoint, or ``no host span`` where the host was outside every layer.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def text(self) -> str:
        """Name and every stat value, for matching an operation by name."""
        return " ".join([self.name] + [v for _, v in self.stats])


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_xplane(path: str) -> List[Event]:
    """Every event of the device planes' op lines and of the host planes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for e in line.events:
                if not on_device and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 tuple((str(k), str(v)) for k, v in e.stats)))
    return out


def _merged(intervals: Iterable[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """Union of intervals clipped to [lo, hi], as sorted disjoint pairs."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: List[Event]                 # device operations inside the window
    gaps: List[Tuple[str, float]]    # (host span, idle seconds), longest first

    def op_time(self, *patterns: str) -> Tuple[int, float]:
        """(count, summed device seconds) of the operations whose name or
        stats contain every one of ``patterns``, averaged over the devices."""
        hit = [e for e in self.ops if all(p in e.text() for p in patterns)]
        return (len(hit) // max(self.n_devices, 1),
                sum(e.dur_ns for e in hit) / 1e9 / max(self.n_devices, 1))

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = {}
        for e in self.ops:
            tot[e.name] = tot.get(e.name, 0.0) + e.dur_ns / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def _label(mid: float, spans: Sequence[Event]) -> str:
    cover = [s for s in spans if s.start_ns <= mid <= s.end_ns]
    if not cover:
        return "no host span"
    return min(cover, key=lambda s: s.dur_ns).name


def summarize(events: Sequence[Event], window_ns: float) -> TraceSummary:
    """Busy and idle time of the device planes inside [0, window_ns]."""
    devices = sorted({e.plane for e in events
                      if e.plane.startswith(DEVICE_PREFIX)})
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)]
    ops, busy, gaps = [], 0.0, []
    for dev in devices:
        dev_ops = [e for e in events if e.plane == dev
                   and e.end_ns > 0 and e.start_ns < window_ns]
        ops += dev_ops
        merged = _merged(((e.start_ns, e.end_ns) for e in dev_ops),
                         0.0, window_ns)
        busy += sum(b - a for a, b in merged)
        edges = [0.0] + [x for ab in merged for x in ab] + [window_ns]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label((a + b) / 2, spans), (b - a) / 1e9))
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(window_s=window_ns / 1e9, busy_s=busy / 1e9 / n,
                        n_devices=len(devices), ops=ops, gaps=gaps)
