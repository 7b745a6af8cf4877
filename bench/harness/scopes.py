"""The program's named scopes on the device operations of a traced run.

The program wraps its device work in ``jax.named_scope``s (``stage0``,
``rescore``, and below them ``stage0/probe``, ``stage0/member_mask``, ...).
XLA keeps a scope path as each operation's ``op_name``, and the profiler
writes it into the ``tf_op`` stat of the operation's event metadata on the
device planes, for example ``jit(progressive_search)/stage0/jit(
truncated_search)/dot_general``.  ``jax.profiler.ProfileData`` shows an
event's own stats but not its metadata's, so this module reads the
metadata from the ``.xplane.pb`` protobuf itself (its wire format: no
schema module is needed) and maps each device operation's name, the HLO
instruction text that `harness.trace` keys operations by, to its scope
paths.  It also counts the dispatches inside the traced window from the
program's own ``repro.engine.enqueue`` host spans: the engine's counters
snapshot around the trace also count the dispatches made while the
profiler writes its file, after the window.

The trace of a run is the newest one under the harness's run directories,
taken only if it names every operation of the run's own reduction.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import tempfile
from typing import Dict, Iterator, List, Optional, Set, Tuple

from harness import trace as tr

SCOPE_STAT = "tf_op"
# the program's host span around one dispatch's enqueue; its ``bucket``
# arg is the dispatch's padded batch size
DISPATCH_SPAN = "repro.engine.enqueue"


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) pairs of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _entry(buf) -> Tuple[int, memoryview]:
    """(key, value) of a map<int64, message> entry."""
    key, val = 0, memoryview(b"")
    for num, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def device_scopes(xspace: bytes) -> Dict[str, Set[str]]:
    """Device operation name -> the scope paths (``tf_op``) it carries,
    over every device plane of a serialized XSpace."""
    out: Dict[str, Set[str]] = {}
    for num, plane in fields(xspace):
        if num != 1:                               # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in fields(plane):
            if pnum == 2:                          # XPlane.name
                name = _str(v)
            elif pnum == 4:                        # event_metadata
                events.append(_entry(v)[1])
            elif pnum == 5:                        # stat_metadata
                sid, meta = _entry(v)
                stat_names[sid] = next(
                    (_str(x) for n, x in fields(meta) if n == 2), "")
        if not name.startswith(tr.DEVICE_PREFIX):
            continue
        scope_ids = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        for meta in events:
            op, scopes = "", set()
            for mnum, v in fields(meta):
                if mnum == 2:                      # XEventMetadata.name
                    op = _str(v)
                elif mnum == 5:                    # XEventMetadata.stats
                    stat = dict(fields(v))
                    if stat.get(1) not in scope_ids:
                        continue
                    if 5 in stat:                  # str_value
                        scopes.add(_str(stat[5]))
                    elif 7 in stat:                # ref_value
                        scopes.add(stat_names.get(stat[7], ""))
            if op:
                out.setdefault(op, set()).update(scopes)
    return out


def newest_trace(root: Optional[str] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` under the harness's run directories."""
    root = root or tempfile.gettempdir()
    paths = glob.glob(os.path.join(root, "bench-run-*", "trace", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


@dataclasses.dataclass
class RunScopes:
    """What a run's trace says beyond `harness.trace`'s reduction."""

    ops: Dict[str, Set[str]]         # device operation -> its scope paths
    dispatches: List[int]            # bucket of each dispatch in the window


def host_spans(path: str, name: str) -> List[tr.Event]:
    """The host plane's events called ``name``, with their args as stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    out.append(tr.Event(
                        plane.name, line.name, e.name, float(e.start_ns),
                        float(e.duration_ns),
                        tuple((str(k), str(v)) for k, v in e.stats)))
    return out


def of_run(ctx, root: Optional[str] = None) -> Optional[RunScopes]:
    """Scope paths and dispatches of the run's own trace; None where there
    is no trace, or the newest one is not this run's."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    path = newest_trace(root)
    if path is None:
        return None
    with open(path, "rb") as f:
        ops = device_scopes(f.read())
    if any(e.name not in ops for e in ctx.trace.ops):
        return None
    window_ns = ctx.trace.window_s * 1e9
    dispatches = [int(dict(e.stats).get("bucket", 0))
                  for e in host_spans(path, DISPATCH_SPAN)
                  if 0 <= e.start_ns < window_ns]
    return RunScopes(ops=ops, dispatches=dispatches)


def per_dispatch(ctx, pattern: str, root: Optional[str] = None
                 ) -> Optional[Tuple[float, List[int]]]:
    """(device seconds of the traced window's operations whose scope path
    contains ``pattern``, averaged over the devices; the bucket of each
    dispatch the program enqueued in the window).  None where no operation
    carries a scope path or no dispatch span was traced (a program without
    named scopes or spans)."""
    run = of_run(ctx, root)
    if run is None or not run.dispatches \
            or not any(run.ops.get(e.name) for e in ctx.trace.ops):
        return None
    hit = [e for e in ctx.trace.ops
           if any(pattern in s for s in run.ops[e.name])]
    seconds = sum(e.dur_ns for e in hit) / 1e9 / max(ctx.trace.n_devices, 1)
    return seconds, run.dispatches
