"""The program's named scopes and dispatch spans read from a profiler trace,
and the readers built on them, on synthetic traces."""

import os
import sys
import types

import pytest

from harness import scopes, spec
from harness import trace as tr

DEV, HOST = "/device:TPU:0", "/host:CPU"


# -- a minimal protobuf writer for synthetic XSpace files ---------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields if v is not None)


def _plane(name, ops, stat_names, events=()):
    """XPlane with event metadata ``ops`` ({id: (name, [(stat id, str or
    ("ref", id))])}), stat metadata ``stat_names`` ({id: name}) and one
    line of ``events`` ([(metadata id, offset ps, duration ps, [(stat id,
    int)])])."""
    parts = [(2, name)]
    for mid, (op, stats) in ops.items():
        st = [_msg((1, sid), (7, v[1]) if isinstance(v, tuple) else (5, v))
              for sid, v in stats]
        meta = _msg((1, mid), (2, op), *[(5, s) for s in st])
        parts.append((4, _msg((1, mid), (2, meta))))
    for sid, sname in stat_names.items():
        parts.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    if events:
        evs = [_msg((1, mid), (2, off), (3, dur),
                    *[(4, _msg((1, sid), (4, v))) for sid, v in st])
               for mid, off, dur, st in events]
        parts.append((3, _msg((1, 1), (2, "python"), (3, 0),
                              *[(4, e) for e in evs])))
    return _msg(*parts)


OPS = {
    1: ("%slice.26 = f32[8,2] slice(f32[8,4] %db)",
        [(10, "jit(progressive_search)/stage0/jit(truncated_search)/slice")]),
    2: ("%while.17 = (s32[]) while(...)",
        [(10, ("ref", 11))]),
    3: ("%fusion.3 = f32[4] fusion(...)",
        [(10, "jit(progressive_search)/rescore/gather"), (12, "other")]),
    4: ("%copy-start = (pred[8]) copy-start(...)", []),
}
STATS = {10: "tf_op", 11: "jit(progressive_search)/stage0/jit(truncated_search)/while",
         12: "hlo_category"}


def xspace(enqueues=((1_000, 4), (400_000, 1))):
    host = _plane(HOST, {1: ("repro.engine.enqueue", []),
                         2: ("repro.engine.sync", [])}, {5: "bucket"},
                  events=[(1, t * 1000, 50_000, [(5, b)])
                          for t, b in enqueues]
                  + [(2, 60_000_000, 10_000, [])])
    return _msg((1, _plane(DEV, OPS, STATS)), (1, host))


def test_device_scopes_by_string_and_by_reference():
    table = scopes.device_scopes(xspace())
    assert table[OPS[1][0]] == {OPS[1][1][0][1]}
    assert table[OPS[2][0]] == {STATS[11]}
    assert table[OPS[3][0]] == {"jit(progressive_search)/rescore/gather"}
    assert table[OPS[4][0]] == set()
    # host planes carry no device operations
    assert "repro.engine.enqueue" not in table


def write_run(root, data: bytes) -> str:
    d = os.path.join(root, "bench-run-x", "trace", "plugins", "profile", "t")
    os.makedirs(d)
    path = os.path.join(d, "h.xplane.pb")
    with open(path, "wb") as f:
        f.write(data)
    return path


def ev(name, start, dur):
    return tr.Event(DEV, tr.OPS_LINE, name, float(start), float(dur))


def context(trace, backend="flat", bucket_counts=None):
    cell = types.SimpleNamespace(config={
        "n_docs": 1000, "schedule": {"d_start": 128},
        "engine": {"backend": {"backend": backend}}})
    return types.SimpleNamespace(
        cell=cell, trace=trace, t0=0.0,
        traced={"n_batches": 99, "bucket_counts": bucket_counts or {1: 99}},
        peaks={"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12})


def summary(window_ns=1e6):
    ops = [ev(OPS[1][0], 100, 200), ev(OPS[2][0], 300, 300),
           ev(OPS[3][0], 600, 100), ev(OPS[4][0], 700, 50)]
    return tr.summarize(ops, window_ns)


def test_dispatches_are_counted_inside_the_window(tmp_path):
    write_run(str(tmp_path), xspace(enqueues=((1_000, 4), (400_000, 1),
                                              (2_000_000, 2))))
    run = scopes.of_run(context(summary()), root=str(tmp_path))
    # the third enqueue starts after the 1 ms window: not this window's
    assert run.dispatches == [4, 1]


def test_stage0_and_rescore_device_ms_per_dispatch(tmp_path):
    write_run(str(tmp_path), xspace())
    ctx = context(summary())
    sec, n = scopes.per_dispatch(ctx, "/stage0/", root=str(tmp_path))
    assert sec == pytest.approx(500e-9) and n == [4, 1]
    assert scopes.per_dispatch(ctx, "/rescore/", root=str(tmp_path))[0] \
        == pytest.approx(100e-9)
    old = scopes.tempfile.gettempdir
    scopes.tempfile.gettempdir = lambda: str(tmp_path)
    try:
        assert spec.reader("stage0_device_ms.poisson")(ctx) == \
            pytest.approx(1e3 * 500e-9 / 2)
        assert spec.reader("rescore_device_ms.closed")(ctx) == \
            pytest.approx(1e3 * 100e-9 / 2)
        # least time of a dispatch: max(1000 * (2 * 128 + 5) B / 1 GB/s,
        # 2 * 1000 * 128 * b / 1 TFLOP/s); memory-bound for both buckets
        least = 2 * 1000 * (2 * 128 + 5) / 1e9
        assert spec.reader("flat_stage0_roofline")(ctx) == \
            pytest.approx(100 * least / 500e-9)
        assert spec.reader("flat_stage0_roofline")(
            context(summary(), backend="ivf")) is None
    finally:
        scopes.tempfile.gettempdir = old


def test_a_program_without_scopes_reads_nothing(tmp_path):
    bare = {k: (v[0], []) for k, v in OPS.items()}
    write_run(str(tmp_path), _msg((1, _plane(DEV, bare, STATS))))
    assert scopes.per_dispatch(context(summary()), "/stage0/",
                               root=str(tmp_path)) is None


def test_another_runs_trace_is_not_read(tmp_path):
    write_run(str(tmp_path), xspace())
    ctx = context(tr.summarize([ev("%not.in.that.trace = f32[1]", 0, 10)],
                               1e6))
    assert scopes.of_run(ctx, root=str(tmp_path)) is None
    assert scopes.of_run(context(None), root=str(tmp_path)) is None
    assert scopes.of_run(context(summary()), root=str(tmp_path / "no")) \
        is None


def test_setup_compile_s_sums_the_log_before_the_window(monkeypatch):
    log = types.SimpleNamespace(events=lambda: [
        (1.0, 2.5, "jit(a)"), (2.0, 0.5, "jit(b)"), (9.0, 4.0, "jit(ref)")])
    monkeypatch.setitem(sys.modules, "repro.obs",
                        types.SimpleNamespace(COMPILES=log))
    read = spec.reader("setup_compile_s")
    assert read(types.SimpleNamespace(t0=5.0)) == pytest.approx(3.0)
    # a program that keeps no compile log
    monkeypatch.setitem(sys.modules, "repro.obs", types.SimpleNamespace())
    assert read(types.SimpleNamespace(t0=5.0)) is None
