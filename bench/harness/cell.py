"""One run of one cell: set-up, the measured window, the reference, and the
result line.

One process holds the chip.  It generates the corpus on the device from the
seed, loads it through ``RetrievalEngine.add_docs``, builds the index, warms
every bucket, and serves ``POST /v1/search`` through ``serve_in_thread`` ->
``EngineDriver`` -> ``RetrievalEngine.execute_batch`` -> backend.  The load
comes from `loadgen` processes that never import JAX.  After the window the
server and engine are released, and the configuration's plain reference
scores every answer the window returned.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import select
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import check, spec, stats
from harness import trace as tr

LOADGEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "loadgen.py")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Log:
    def __init__(self):
        self.prefix = "[bench]"

    def __call__(self, msg: str) -> None:
        print(f"{self.prefix} {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a metric reader sees of one run."""

    cell: spec.Cell
    t0: float
    t1: float
    setup_s: float
    rec: Dict[str, np.ndarray]           # every request the clients sent
    recall: float
    window: Dict                         # engine counters over the window
    traced: Optional[Dict] = None        # engine counters over the trace
    trace: Optional[tr.TraceSummary] = None
    kernel: Optional[Dict] = None        # shapes of the fused stage-0 pack
    peaks: Optional[Dict] = None

    @property
    def due_in_window(self) -> np.ndarray:
        return stats.in_window(self.rec, self.t0, self.t1)


def devices(require_tpu: bool, chips: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from None
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def plans(t: Dict, seed: int, seconds: float, order: np.ndarray,
          pool_path: str, rundir: str) -> List[str]:
    """Write one plan file per load generator process for the traffic mix
    ``t``; returns the paths."""
    procs = int(t["procs"])
    base = {"pool": pool_path, "k": int(t["k"]),
            "timeout_s": float(t["timeout_s"])}
    out = []
    if t["arrivals"] == "poisson":
        # a Poisson process given its count: a fixed number of arrivals,
        # uniform over the window.  They are drawn from the mix's own
        # ``arrival_seed``, so every run offers the same arrivals and its
        # seed changes only the corpus, the pool and the pool's order
        n = int(round(float(t["rate_per_s"]) * seconds))
        rng = np.random.default_rng([int(t["arrival_seed"]), 1])
        offsets = np.sort(rng.uniform(0.0, seconds, n))
        qidx = order[np.arange(n) % order.size]
        for j in range(procs):
            out.append(dict(base, mode="open",
                            connections=int(t["connections_per_proc"]),
                            offsets=offsets[j::procs].tolist(),
                            qidx=qidx[j::procs].tolist()))
    elif t["arrivals"] == "closed":
        clients = int(t["clients"])
        if clients % procs:
            raise ValueError(f"{clients} clients do not split over "
                             f"{procs} processes")
        starts = (np.arange(clients) * (order.size // clients)).tolist()
        per = clients // procs
        for j in range(procs):
            out.append(dict(base, mode="closed", connections=per,
                            order=order.tolist(),
                            starts=starts[j * per:(j + 1) * per]))
    else:
        raise ValueError(f"unknown arrivals {t['arrivals']!r}")
    paths = []
    for j, plan in enumerate(out):
        plan["out"] = os.path.join(rundir, f"rec{j}.npz")
        paths.append(os.path.join(rundir, f"plan{j}.json"))
        with open(paths[-1], "w") as f:
            json.dump(plan, f)
    return paths


class Clients:
    """The load generator processes and their line protocol."""

    def __init__(self, plan_paths: List[str]):
        self.procs = [subprocess.Popen(
            [sys.executable, LOADGEN, p], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, bufsize=1)
            for p in plan_paths]
        self.plans = plan_paths

    def expect(self, word: str, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        for p in self.procs:
            left = deadline - time.monotonic()
            ready, _, _ = select.select([p.stdout], [], [], max(left, 0))
            line = p.stdout.readline().strip() if ready else ""
            if line != word:
                raise RuntimeError(f"load generator {p.pid}: expected "
                                   f"{word!r}, got {line!r} "
                                   f"(exit {p.poll()})")

    def tell(self, line: str) -> None:
        for p in self.procs:
            p.stdin.write(line + "\n")
            p.stdin.flush()

    def records(self) -> Dict[str, np.ndarray]:
        parts = []
        for path in self.plans:
            with open(path) as f:
                out = json.load(f)["out"]
            with np.load(out) as z:
                parts.append({k: z[k] for k in z.files})
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                if f is not None:
                    f.close()


def snapshot(engine) -> Dict:
    with engine.lock:
        s = engine.stats
        return {"n_batches": s.n_batches, "n_completed": s.n_completed,
                "n_compiles": s.n_compiles,
                "bucket_counts": dict(s.bucket_counts),
                "batch_ms": list(s.compute_ms)}


def delta(a: Dict, b: Dict) -> Dict:
    n = b["n_batches"] - a["n_batches"]
    buckets = {k: v - a["bucket_counts"].get(k, 0)
               for k, v in b["bucket_counts"].items()}
    return {"n_batches": n, "n_completed": b["n_completed"] - a["n_completed"],
            "n_compiles": b["n_compiles"] - a["n_compiles"],
            "bucket_counts": {k: v for k, v in buckets.items() if v},
            "batch_ms": b["batch_ms"][-n:] if n else []}


def sleep_until(t: float) -> None:
    left = t - time.monotonic()
    if left > 0:
        time.sleep(left)


def spans(engine, server) -> None:
    """Name the host's layers in the profiler trace: the HTTP handler, the
    driver's call into the engine, and the engine's device dispatch."""
    import jax

    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def inner(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        setattr(obj, attr, inner)

    wrap(server, "_do_search", "bench.http_search")
    wrap(engine, "execute_batch", "bench.execute_batch")
    wrap(engine, "_dispatch", "bench.dispatch")


def kernel_shapes(engine, cfg: Dict) -> Optional[Dict]:
    state = engine.index_state
    pack = state.data.get("pack") if state is not None else None
    if pack is None:
        return None
    return {"n_probe": min(int(cfg["engine"]["backend"]["n_probe"]),
                           int(state.data["n_lists"])),
            "max_len": int(pack["max_len"]), "d0": int(pack["dim"]),
            "member_bytes": int(pack["rows"].dtype.itemsize),
            "k": int(engine.sched.stages[0].k)}


class Served:
    """The system under test, loaded, warmed and serving over HTTP; a
    context manager that stops the server and driver and drops the engine."""

    def __init__(self, cfg: Dict, corpus, *, trace: bool = False,
                 tamper: Optional[Callable] = None):
        import jax

        from repro.engine import EngineConfig, EngineDriver, RetrievalEngine
        from repro.serve import serve_in_thread

        engine = RetrievalEngine(config=EngineConfig.from_dict(
            dict(cfg["engine"], d_emb=corpus.dim, capacity=corpus.n_docs)))
        for b in range(corpus.n_blocks):
            engine.add_docs(corpus.block(b))
        engine.maybe_rebuild(force=True)
        jax.block_until_ready([x for x in jax.tree.leaves(
            engine.index_state.data) if isinstance(x, jax.Array)])
        engine.warmup()
        self.kernel = kernel_shapes(engine, cfg)
        if tamper is not None:
            tamper(engine)
        self.engine = engine
        self.driver = EngineDriver(engine, **cfg["driver"]).start()
        self.handle = serve_in_thread(engine, self.driver,
                                      require_tenant=False)
        if trace:
            spans(engine, self.handle.server)

    def __enter__(self) -> "Served":
        return self

    def __exit__(self, *exc) -> None:
        self.handle.stop()
        self.driver.stop()
        self.engine = self.driver = self.handle = None
        gc.collect()


def measure(served: Served, clients: Clients, seconds: float, trace: bool,
            traffic: Dict, rundir: str, device) -> Dict:
    """Start the clients on a common window and wait for their records;
    returns the window's times and the engine's counters."""
    engine = served.engine
    clients.expect("encoded", 300)
    clients.tell(f"url {served.handle.url}")
    clients.expect("ready", 120)
    t0 = time.monotonic() + 0.3
    t1 = t0 + seconds
    clients.tell(f"go {t0!r} {t1!r}")
    sleep_until(t0)
    s0 = snapshot(engine)
    out = {"t0": t0, "t1": t1, "traced": None, "trace_ns": None}
    if trace:
        out["trace_ns"], out["traced"] = trace_window(
            engine, traffic, t0, seconds, rundir)
    sleep_until(t1)
    out["window"] = delta(s0, snapshot(engine))
    clients.expect("done", float(traffic["timeout_s"]) + 60)
    out["memory_peak"] = int((device.memory_stats() or {}).get(
        "peak_bytes_in_use", 0))
    return out


def start(cell: spec.Cell, require_tpu: bool, log: Log):
    """Find the chip and turn the compile cache on; returns the devices."""
    devs = devices(require_tpu, cell.chips)
    dev = devs[0]
    log.prefix = f"[{dev.platform} {dev.device_kind} x{len(devs)}]"
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    log(f"cell {cell.name}; compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devs


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        tamper: Optional[Callable] = None, log: Optional[Log] = None) -> Dict:
    """One run; returns the result object.  Raises NoChip before any work
    when the chip is missing."""
    log = log or Log()
    devs = start(cell, require_tpu, log)
    rundir = tempfile.mkdtemp(prefix="bench-run-")
    try:
        return _run(cell, seed, seconds, trace, t_start, tamper, log, devs,
                    rundir)
    finally:
        shutil.rmtree(rundir, ignore_errors=True)


def pool(cell: spec.Cell, seed: int, rundir: str):
    """(corpus, queries, the row each query copies (-1 for a fresh draw),
    order, path of the pool file the clients read)."""
    from harness.corpus import Corpus

    cfg, traffic = cell.config, cell.traffic
    corpus = Corpus(seed, int(cfg["n_docs"]), int(cfg["dim"]), cfg["corpus"])
    queries, sources, order = corpus.pool(int(traffic["pool"]),
                                          float(traffic["copy_share"]))
    path = os.path.join(rundir, "pool.npy")
    np.save(path, queries)
    return corpus, queries, sources, order, path


def _run(cell, seed, seconds, trace, t_start, tamper, log, devs, rundir):
    dev = devs[0]
    cfg, traffic = cell.config, cell.traffic
    corpus, queries, sources, order, pool_path = pool(cell, seed, rundir)
    # the clients encode their requests while the engine loads and builds
    clients = Clients(plans(traffic, seed, seconds, order, pool_path, rundir))
    try:
        with Served(cfg, corpus, trace=trace, tamper=tamper) as served:
            w = measure(served, clients, seconds, trace, traffic, rundir, dev)
            kernel = served.kernel
        rec = clients.records()
    finally:
        clients.close()
    t0, t1, window = w["t0"], w["t1"], w["window"]
    setup_s = t0 - t_start
    log(f"seed {seed}: setup {setup_s:.2f}s; window {window['n_batches']} "
        f"batches, {window['n_completed']} searches, {window['n_compiles']} "
        f"compiles, buckets {window['bucket_counts']}; kernel {kernel}")
    summary = None
    if trace:
        summary = tr.summarize(tr.read_xplane(tr.find_xplane(
            os.path.join(rundir, "trace"))), w["trace_ns"])

    # the reference runs with the program's state freed: the peak above is
    # the program's alone
    t_ref = time.monotonic()
    ref = reference(cfg, corpus, int(traffic["k"]))
    exact_ids, prog_ids, _ = ref.search(queries)
    values = check.numbers(rec, queries, sources, ref, prog_ids,
                           corpus.n_docs)
    ok, checks = check.judge(values, cfg["correct"])
    due = stats.in_window(rec, t0, t1)
    recall = check.recall(rec["ids"][due], rec["status"][due],
                          exact_ids[rec["qidx"][due]])
    del ref
    log(f"reference: {time.monotonic() - t_ref:.2f}s; compared "
        f"{rec['status'].size} answers: "
        + ", ".join(f"{n}={v!r}" for n, v in values.items()))

    ctx = Context(cell=cell, t0=t0, t1=t1, setup_s=setup_s,
                  rec=rec, recall=recall, window=window, traced=w["traced"],
                  trace=summary, kernel=kernel,
                  peaks=(spec.peaks(dev.device_kind)
                         if dev.platform == "tpu" else None))
    late = stats.lateness_ms(rec)
    print(json.dumps({"lateness_p95_ms": stats.percentile(late, 95),
                      "lateness_max_ms": float(late.max()),
                      "platform": dev.platform, "device_kind": dev.device_kind,
                      "device_count": len(devs)}), flush=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(ok), "attempted": int(np.count_nonzero(due)),
              "failed": int(np.count_nonzero(rec["status"][due] != 200)),
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs),
                         "memory_peak_bytes": w["memory_peak"]}}
    if trace:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in summary.top_ops(10)],
            "idle_gaps": [[f"idle in {n}", s] for n, s in summary.gaps[:10]]}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def reference(cfg: Dict, corpus, k: int, control: Optional[Dict] = None):
    """The configuration's plain reference over the regenerated corpus; with
    ``control`` (a configuration's ``control`` block), the control that the
    block describes."""
    mod = spec.reference(cfg["reference"])
    s = cfg["schedule"]
    return mod.Reference(corpus.rows(), mod.schedule(
        s["d_start"], corpus.dim, s["k0"], s["final_k"]), k_exact=k,
        **(control or {}))


def trace_window(engine, traffic, t0, seconds, rundir):
    """Trace a steady part of the window into ``rundir/trace``; returns
    (traced nanoseconds, engine counters over the trace)."""
    import jax

    skip = min(float(traffic["trace_skip_s"]), 0.25 * seconds)
    length = min(float(traffic["trace_s"]), seconds - skip - 0.5)
    sleep_until(t0 + skip)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    log_dir = os.path.join(rundir, "trace")
    a = snapshot(engine)
    t_a = time.monotonic()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    sleep_until(t_a + length)
    t_b = time.monotonic()
    jax.profiler.stop_trace()
    b = snapshot(engine)
    return (t_b - t_a) * 1e9, delta(a, b)
