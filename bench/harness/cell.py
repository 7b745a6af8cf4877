"""One run of one cell: set-up, the measured window, the reference, and the
result line.

One process holds the chip.  It generates the corpus on the device from the
seed, loads it through ``RetrievalEngine.add_docs``, builds the index, warms
every bucket, and serves ``POST /v1/search`` through ``serve_in_thread`` ->
``EngineDriver`` -> ``RetrievalEngine.execute_batch`` -> backend.  The load
comes from `loadgen` processes that never import JAX.  After the window the
server and engine are released, and the configuration's plain reference
scores every answer the window returned.

A configuration with ``"replicas": N`` (N > 1) is served as N one-chip
replicas behind the program's router (`Tier`): this process is replica 0 and
holds chip 0 alone; replicas 1..N-1 are `replica` processes, one chip each,
that build their engines exactly as this one does; the router is the
program's own launcher (``repro.launch.serve --role router``) in a process
that holds no chip, and the load generators send to it.  The per-layer
readers see replica 0: its engine's counters, its compile log and its
chip's trace.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import select
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from typing import Callable, Dict, List, Optional

import numpy as np

from harness import check, spec, stats
from harness import trace as tr

HARNESS = os.path.dirname(os.path.abspath(__file__))
LOADGEN = os.path.join(HARNESS, "loadgen.py")
REPLICA = os.path.join(HARNESS, "replica.py")


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
    served: Optional[List[int]] = None   # searches each replica served in
                                         # the window (router's counters)

    @property
    def due_in_window(self) -> np.ndarray:
        return stats.in_window(self.rec, self.t0, self.t1)


def replicas(cfg: Dict) -> int:
    """How many one-chip replicas serve the configuration (1: this process
    alone)."""
    return int(cfg.get("replicas", 1))


def pin(chip: int) -> Dict[str, str]:
    """Environment that holds a process to chip ``chip`` of the host alone,
    through the TPU runtime's per-process settings; it has to be set before
    JAX starts in that process.  On a v5e host this set gives each of four
    processes one chip of its own."""
    port = free_port()
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}"}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def src_dir() -> str:
    """The directory that holds the ``repro`` package this process
    imports, for the processes it starts."""
    return os.path.dirname(os.path.dirname(
        importlib.util.find_spec("repro").origin))


def http_json(url: str, timeout: float = 5.0) -> Dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.load(r)


def read_line(p: subprocess.Popen, timeout: float) -> str:
    """The next line the child ``p`` writes, or "" after ``timeout``
    seconds or at its end."""
    ready, _, _ = select.select([p.stdout], [], [], max(timeout, 0))
    return p.stdout.readline().strip() if ready else ""


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
            line = read_line(p, deadline - time.monotonic())
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


class Tier:
    """Replicas 1..N-1 of a ``replicas`` configuration, each a `replica`
    process on a chip of its own, and the program's router in front of all
    N; replica 0 is this process's `Served`.

    The replica processes start as soon as the tier is made; `found` lets
    them build once each has found its chip, alongside replica 0, and
    `front` waits for them and starts the router.  ``fault`` (a name in
    `harness.faults.PLANTED`) is planted in replica 1."""

    def __init__(self, cell: spec.Cell, seed: int, trace: bool, rundir: str,
                 require_tpu: bool, log: Log, fault: Optional[str] = None):
        self.t_made = time.monotonic()
        self.log = log
        self.n = replicas(cell.config)
        self.rundir = rundir
        self.src = src_dir()
        self.logs: List[str] = []
        self.router: Optional[subprocess.Popen] = None
        self.url = ""
        self.urls: List[str] = []
        self.procs: List[subprocess.Popen] = []
        for i in range(1, self.n):
            plan = os.path.join(rundir, f"replica{i}.json")
            with open(plan, "w") as f:
                json.dump({"config": cell.config, "seed": seed,
                           "trace": trace, "require_tpu": require_tpu,
                           "fault": fault if i == 1 else None}, f)
            env = self._env(pin(i) if require_tpu else {})
            self.procs.append(self._spawn(f"replica{i}", [
                sys.executable, REPLICA, plan], env, pipes=True))

    def _env(self, extra: Dict[str, str]) -> Dict[str, str]:
        path = os.environ.get("PYTHONPATH")
        return dict(os.environ, PYTHONPATH=self.src + (
            os.pathsep + path if path else ""), **extra)

    def _spawn(self, tag: str, cmd: List[str], env: Dict[str, str],
               pipes: bool = False) -> subprocess.Popen:
        self.logs.append(os.path.join(self.rundir, f"{tag}.log"))
        with open(self.logs[-1], "w") as log:
            pipe = subprocess.PIPE if pipes else subprocess.DEVNULL
            return subprocess.Popen(
                cmd, env=env, stdin=pipe, stdout=pipe if pipes else log,
                stderr=log, text=True, bufsize=1)

    def _expect(self, p: subprocess.Popen, word: str, timeout: float) -> str:
        line = read_line(p, timeout)
        tag, _, rest = line.partition(" ")
        if tag == "nochip":
            raise NoChip(f"replica process {p.pid}: {rest}")
        if tag != word:
            raise RuntimeError(f"replica process {p.pid}: expected {word!r}, "
                               f"got {line!r} (exit {p.poll()})")
        return rest

    def found(self, device) -> None:
        """Wait until every replica has found its chip, then let them all
        build.  No process of the tier does device work while another is
        still starting its TPU runtime: on a four-chip v5e host one run in
        fifteen hung where replica 0's first device work overlapped the
        other replicas' start."""
        for p in self.procs:
            found = json.loads(self._expect(p, "device", 300))
            if (found["platform"], found["kind"]) != (device.platform,
                                                      device.device_kind):
                raise NoChip(f"replica process {p.pid} found {found}, not "
                             f"a {device.platform} {device.device_kind}")
        self.t_found = time.monotonic()
        for p in self.procs:
            p.stdin.write("build\n")
            p.stdin.flush()

    def front(self, url0: str) -> None:
        """Wait for every replica to serve, start the router over all N,
        and wait until it probes all N ready."""
        t_own = time.monotonic()
        self.urls = [url0] + [self._expect(p, "url", 900)
                              for p in self.procs]
        t_all = time.monotonic()
        port = free_port()
        self.router = self._spawn("router", [
            sys.executable, "-m", "repro.launch.serve", "--serve-http",
            "--role", "router", "--replicas", ",".join(self.urls),
            "--port", str(port), "--hedge-ms", "-1"],
            self._env({"JAX_PLATFORMS": "cpu"}))
        self.url = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if self.router.poll() is not None:
                raise RuntimeError(f"the router exited with "
                                   f"{self.router.returncode}")
            try:
                if http_json(self.url + "/v1/replicas")["n_ready"] == self.n:
                    self.log(f"tier, seconds after the replicas started: "
                             f"all found their chips "
                             f"{self.t_found - self.t_made:.2f}, replica 0 "
                             f"served {t_own - self.t_made:.2f}, all "
                             f"{t_all - self.t_made:.2f}, the router ready "
                             f"{time.monotonic() - self.t_made:.2f}")
                    return
            except OSError:
                pass
            time.sleep(0.1)
        raise RuntimeError(f"the router found fewer than {self.n} replicas "
                           f"ready in 120 s")

    def served(self) -> List[int]:
        """The router's count of searches each replica has served."""
        by_url = {r["url"]: r["n_served"]
                  for r in http_json(self.url + "/v1/replicas")["replicas"]}
        return [by_url[u.rstrip("/")] for u in self.urls]

    def cpu_s(self) -> List[float]:
        """CPU seconds used so far by replica 0 (this process), replicas
        1..N-1 and the router, in that order (Linux ``/proc``)."""
        tick = os.sysconf("SC_CLK_TCK")
        out = [time.process_time()]
        for p in self.procs + [self.router]:
            with open(f"/proc/{p.pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            out.append((int(fields[11]) + int(fields[12])) / tick)
        return out

    def trace(self, t_a: float, length: float) -> None:
        """Have replicas 1..N-1 trace their chips over the same span."""
        for p in self.procs:
            p.stdin.write(f"trace {t_a!r} {length!r}\n")
            p.stdin.flush()

    def stop(self) -> List[Dict]:
        """Stop the router, then each replica; returns what each replica
        reported of its chip (memory peak, traced busy seconds)."""
        self._stop_router()
        for p in self.procs:
            p.stdin.write("stop\n")
            p.stdin.flush()
        out = [json.loads(self._expect(p, "stopped", 120))
               for p in self.procs]
        for p in self.procs:
            p.wait(60)
        return out

    def _stop_router(self) -> None:
        if self.router is not None and self.router.poll() is None:
            self.router.send_signal(signal.SIGTERM)
            try:
                self.router.wait(10)
            except subprocess.TimeoutExpired:
                self.router.kill()
                self.router.wait()

    def close(self) -> None:
        """End whatever still runs, and wait for it."""
        self._stop_router()
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                f.close()

    def tails(self, n: int = 2000) -> str:
        """The end of each replica's and the router's log."""
        out = []
        for path in self.logs:
            with open(path, errors="replace") as f:
                out.append(f"--- {os.path.basename(path)}\n{f.read()[-n:]}")
        return "\n".join(out)


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
            traffic: Dict, rundir: str, device,
            tier: Optional[Tier] = None) -> Dict:
    """Start the clients on a common window and wait for their records;
    returns the window's times and the engine's counters.  With a ``tier``
    the clients send to its router, and the replicas are stopped once the
    clients are done."""
    engine = served.engine
    clients.expect("encoded", 300)
    clients.tell(f"url {tier.url if tier else served.handle.url}")
    clients.expect("ready", 120)
    t0 = time.monotonic() + 0.3
    t1 = t0 + seconds
    clients.tell(f"go {t0!r} {t1!r}")
    if tier is not None and trace:
        skip, length = trace_span(traffic, seconds)
        tier.trace(t0 + skip, length)
    sleep_until(t0)
    s0 = snapshot(engine)
    n0 = tier.served() if tier else None
    c0 = tier.cpu_s() if tier else None
    out = {"t0": t0, "t1": t1, "traced": None, "trace_ns": None,
           "served": None, "replicas": []}
    if trace:
        out["trace_ns"], out["traced"] = trace_window(
            engine, traffic, t0, seconds, rundir)
    sleep_until(t1)
    out["window"] = delta(s0, snapshot(engine))
    if tier is not None:
        out["served"] = [b - a for a, b in zip(n0, tier.served())]
        out["cpu_share"] = [round((b - a) / seconds, 3)
                            for a, b in zip(c0, tier.cpu_s())]
    clients.expect("done", float(traffic["timeout_s"]) + 60)
    out["memory_peak"] = int((device.memory_stats() or {}).get(
        "peak_bytes_in_use", 0))
    if tier is not None:
        out["replicas"] = tier.stop()
    return out


def start(cell: spec.Cell, require_tpu: bool, log: Log):
    """Find the chip and turn the compile cache on; returns the devices.
    For a ``replicas`` configuration this process is replica 0 and holds
    chip 0 alone; the other chips are its replicas'."""
    chips = cell.chips
    if replicas(cell.config) > 1:
        chips = 1
        if require_tpu:
            os.environ.update(pin(0))
    devs = devices(require_tpu, chips)
    dev = devs[0]
    log.prefix = f"[{dev.platform} {dev.device_kind} x{len(devs)}]"
    log(f"cell {cell.name}; compile cache {compile_cache()}")
    return devs


def compile_cache() -> str:
    """Turn the persistent compilation cache on for every compile; returns
    its directory."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        tamper: Optional[Callable] = None, fault: Optional[str] = None,
        log: Optional[Log] = None) -> Dict:
    """One run; returns the result object.  Raises NoChip before any work
    when the chip is missing (for a ``replicas`` configuration, chip 0; a
    replica that finds no chip raises it before the window).  ``tamper``
    breaks replica 0's engine in place, ``fault`` (a name in
    `harness.faults.PLANTED`) replica 1's."""
    log = log or Log()
    devs = start(cell, require_tpu, log)
    rundir = tempfile.mkdtemp(prefix="bench-run-")
    tier = None
    try:
        if replicas(cell.config) > 1:
            tier = Tier(cell, seed, trace, rundir, require_tpu, log, fault)
            tier.found(devs[0])
        return _run(cell, seed, seconds, trace, t_start, tamper, log, devs,
                    rundir, tier)
    except BaseException:
        if tier is not None:
            tier.close()
            log(f"replica and router logs:\n{tier.tails()}")
        raise
    finally:
        if tier is not None:
            tier.close()
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


def _run(cell, seed, seconds, trace, t_start, tamper, log, devs, rundir,
         tier):
    dev = devs[0]
    cfg, traffic = cell.config, cell.traffic
    corpus, queries, sources, order, pool_path = pool(cell, seed, rundir)
    # the clients encode their requests while the engine loads and builds
    clients = Clients(plans(traffic, seed, seconds, order, pool_path, rundir))
    try:
        with Served(cfg, corpus, trace=trace, tamper=tamper) as served:
            if tier is not None:
                tier.front(served.handle.url)
            w = measure(served, clients, seconds, trace, traffic, rundir, dev,
                        tier)
            kernel = served.kernel
        rec = clients.records()
    finally:
        clients.close()
    t0, t1, window = w["t0"], w["t1"], w["window"]
    setup_s = t0 - t_start
    log(f"seed {seed}: setup {setup_s:.2f}s; window {window['n_batches']} "
        f"batches, {window['n_completed']} searches, {window['n_compiles']} "
        f"compiles, buckets {window['bucket_counts']}; kernel {kernel}")
    if tier is not None:
        log(f"replicas: {w['served']} searches served in the window; "
            f"replicas 1..{tier.n - 1}: {w['replicas']}; CPU share over the "
            f"window of replicas 0..{tier.n - 1} and the router: "
            f"{w['cpu_share']}")
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
                         if dev.platform == "tpu" else None),
                  served=w["served"])
    count = tier.n if tier is not None else len(devs)
    late = stats.lateness_ms(rec)
    print(json.dumps({"lateness_p95_ms": stats.percentile(late, 95),
                      "lateness_max_ms": float(late.max()),
                      "platform": dev.platform, "device_kind": dev.device_kind,
                      "device_count": count}), flush=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(ok), "attempted": int(np.count_nonzero(due)),
              "failed": int(np.count_nonzero(rec["status"][due] != 200)),
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": count,
                         "memory_peak_bytes": max(
                             [w["memory_peak"]] + [r["memory_peak"]
                                                   for r in w["replicas"]])}}
    if trace:
        # busy seconds averaged over the chips: replica 0's trace and each
        # other replica's own over the same span
        result["device"]["busy_s"] = float(np.mean(
            [summary.busy_s] + [r["busy_s"] for r in w["replicas"]]))
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


def trace_span(traffic: Dict, seconds: float):
    """(seconds into the window, length) of the traced part of a window."""
    skip = min(float(traffic["trace_skip_s"]), 0.25 * seconds)
    return skip, min(float(traffic["trace_s"]), seconds - skip - 0.5)


def trace_window(engine, traffic, t0, seconds, rundir):
    """Trace a steady part of the window into ``rundir/trace``; returns
    (traced nanoseconds, engine counters over the trace)."""
    skip, length = trace_span(traffic, seconds)
    sleep_until(t0 + skip)
    a = snapshot(engine)
    ns = profile(os.path.join(rundir, "trace"), length)
    b = snapshot(engine)
    return ns, delta(a, b)


def profile(log_dir: str, length: float) -> float:
    """Trace this process's devices from now for ``length`` seconds into
    ``log_dir``; returns the traced nanoseconds."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t_a = time.monotonic()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    sleep_until(t_a + length)
    t_b = time.monotonic()
    jax.profiler.stop_trace()
    return (t_b - t_a) * 1e9
