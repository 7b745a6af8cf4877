#!/usr/bin/env python3
"""Serve progressive search on a TPU through the system's own entry points.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # four one-chip replicas behind a router

**One chip.**  The paper's deployment (``repro.configs.paper_rag``: 3584-dim
gte-Qwen2-7B embeddings, Table III schedule d_start 128 -> d_max 3584 with
K 64, final_k 10) over a topically clustered corpus cut from 1M to 262,144
rows: 1M x 3584 f32 alone is 14.3 GB, nearly all of a v5e's 16 GB.  The
store is sized to 262,144 rows up front and loaded with 261,120 through
``RetrievalEngine.add_docs``; the last 1,024 rows are appended after each
index build, so the backends absorb them through their in-place (donated)
scatters.  Each backend then serves 64 queries over HTTP
(``serve_in_thread`` -> ``EngineDriver`` -> ``RetrievalEngine``): 32 noisy
copies of stored rows (16 of them appended ones) and 32 fresh draws from
the same mixture.  The backends run one after another, each released
before the next is built: ``flat``, ``ivf`` with float32 and int8 member
slabs, and ``quantized`` with PQ codes.  ``ivf`` and ``quantized`` must
take the fused Pallas kernel, compiled (not interpreted).

Results are checked against two references written here in plain
``jax.numpy`` at ``Precision.HIGHEST``: an exact full-dimension search, and
the same progressive schedule.  Every backend must find the source row of
>= 99% of the copies at rank 1; ``flat`` must share >= 99% of its top-10
ids with the progressive reference.  recall@10 against the exact search is
printed, not gated: the schedule keeps 10 candidates from 1024 dims on.

**Four chips** (``--chips 4``).  The replicated tier: a CPU-pinned child
writes a 65,536-row corpus as a snapshot into ``--state-dir``; a primary
and three followers (``repro.launch.serve --role=...``), each held to its
own chip, recover from it; a CPU-pinned ``--role=router`` fronts them.
The same queries go to the primary alone and then through the router, and
every replica must return the primary's ids.  This process never imports
JAX: a chip belongs to one process.

The last line of standard output is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  Without a TPU,
or outside a checkout, the script exits nonzero and prints no such line.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import traceback
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# the paper deployment (repro.configs.paper_rag, Table III) and its cuts
DIM = 3584
D_START, K0, FINAL_K = 128, 64, 10
CAPACITY = 262_144           # device rows; 1M x 3584 f32 does not fit 16 GB
N_APPEND = 1_024             # appended after each build (donated scatters)
N_LOAD = CAPACITY - N_APPEND
N_COPIES = 32                # noisy copies of stored rows (half appended)
N_FRESH = 32                 # fresh draws from the corpus mixture
SIGMA = 0.25                 # copy noise, make_clustered_corpus's default
REPLICA_ROWS = 65_536        # --chips 4 corpus
MIN_HIT1 = 0.99
MIN_AGREE = 0.99


def log(msg: str) -> None:
    print(msg, flush=True)


def http(url: str, path: str, body=None, timeout: float = 300.0):
    """One JSON round trip (plain urllib: the --chips 4 parent stays off
    JAX); returns (status, payload, seconds)."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url.rstrip("/") + path, data=data,
        method="GET" if body is None else "POST",
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            out = resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        out = e.code, json.loads(e.read() or b"{}")
    except (urllib.error.URLError, ConnectionError, TimeoutError) as e:
        out = 0, {"error": str(e)}
    return out[0], out[1], time.perf_counter() - t0


def serve_queries(url: str, queries, k: int = FINAL_K):
    """Search each query over HTTP, one at a time; returns (ids (Q, k)
    int64 padded with -1, per-request seconds, served_by list)."""
    import numpy as np

    ids = np.full((len(queries), k), -1, np.int64)
    secs, served_by = [], []
    for j, q in enumerate(queries):
        status, payload, dt = http(url, "/v1/search",
                                   {"query": q.tolist(), "k": k})
        if status != 200:
            raise RuntimeError(f"search {j} -> {status}: {payload}")
        got = payload["ids"][:k]
        ids[j, :len(got)] = got
        secs.append(dt)
        served_by.append(payload.get("served_by"))
    return ids, secs, served_by


def make_data(n_rows: int, seed: int):
    """Seeded clustered corpus + queries: (rows (n_rows, DIM), queries,
    source row of each copy).  Copies point at the last N_APPEND rows for
    their second half; fresh draws come from the same mixture and are not
    stored."""
    import numpy as np
    from repro.rag import make_clustered_corpus

    corpus = make_clustered_corpus(n_rows + N_FRESH, DIM, 1, seed=seed)
    rows, fresh = corpus.db[:n_rows], corpus.db[n_rows:]
    rng = np.random.default_rng(seed + 1)
    half = N_COPIES // 2
    sources = np.concatenate([
        rng.choice(n_rows - N_APPEND, half, replace=False),
        n_rows - N_APPEND + rng.choice(N_APPEND, N_COPIES - half,
                                       replace=False)])
    copies = rows[sources] + SIGMA * corpus.scales * rng.standard_normal(
        (N_COPIES, DIM), dtype=np.float32)
    queries = np.concatenate([copies, fresh]).astype(np.float32)
    return rows, queries, sources


def schedule_stages():
    from repro.core import make_schedule

    sched = make_schedule(D_START, DIM, K0, final_k=FINAL_K)
    return tuple((s.dim, s.k) for s in sched.stages)


def references(rows, queries, stages):
    """(exact top-10, progressive top-10) ids in plain jax.numpy at
    Precision.HIGHEST — written apart from ``repro.core`` on purpose."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    hi = jax.lax.Precision.HIGHEST

    def scores(q, x):                       # rank-equivalent squared L2
        return (jnp.sum(x * x, axis=-1)[None, :]
                - 2.0 * jnp.matmul(q, x.T, precision=hi))

    @jax.jit
    def exact(q, db):
        return jax.lax.top_k(-scores(q, db), FINAL_K)[1]

    @functools.partial(jax.jit, static_argnums=2)
    def progressive(q, db, stages):
        d0, k0 = stages[0]
        cand = jax.lax.top_k(-scores(q[:, :d0], db[:, :d0]), k0)[1]
        for d, k in stages[1:]:
            x = db[cand, :d]                                  # (Q, C, d)
            s = (jnp.sum(x * x, axis=-1)
                 - 2.0 * jnp.einsum("qd,qcd->qc", q[:, :d], x, precision=hi))
            cand = jnp.take_along_axis(cand, jax.lax.top_k(-s, k)[1], axis=1)
        return cand

    db = jax.device_put(rows)
    q = jnp.asarray(queries)
    out = (np.asarray(exact(q, db)), np.asarray(progressive(q, db, stages)))
    del db
    return out


def overlap(got, want) -> float:
    """Mean share of ``want``'s ids per row that ``got`` also holds."""
    import numpy as np

    return float(np.mean([len(set(g) & set(w)) / len(w)
                          for g, w in zip(got.tolist(), want.tolist())]))


# -- one chip ------------------------------------------------------------------
def backend_configs():
    from repro.engine import FlatConfig, IVFConfig, QuantizedConfig

    return [
        ("flat", FlatConfig()),
        ("ivf-f32", IVFConfig(use_kernel="auto", stage0_dtype="float32")),
        ("ivf-int8", IVFConfig(use_kernel="auto", stage0_dtype="int8")),
        ("quantized-pq", QuantizedConfig(codec="pq", use_kernel="auto")),
    ]


def check_kernel_path(name, engine) -> None:
    """The approximate backends must run their fused Pallas kernel,
    compiled for the chip: no XLA reference, no interpret mode."""
    be = engine.backend
    if name == "flat":
        return
    if not be._kernel_enabled() or be._interpret():
        raise AssertionError(
            f"{name}: kernel_enabled={be._kernel_enabled()} "
            f"interpret={be._interpret()}")
    data = engine.index_state.data
    if name.startswith("ivf") and data.get("pack") is None:
        raise AssertionError(f"{name}: no kernel pack (flat fallback?)")


def check_absorbed(name, engine) -> None:
    """Appended rows went through the backend's in-place scatters (IVF
    rows whose nearest list is full ride the tail window instead)."""
    data = engine.index_state.data
    if name.startswith("ivf"):
        done = (data["absorb_upto"] == CAPACITY
                and len(data["tail_pending"]) < N_APPEND)
    elif name.startswith("quantized"):
        done = data["coded_upto"] == CAPACITY
    else:
        done = engine.store.size == CAPACITY
    if not done:
        raise AssertionError(f"{name}: appended rows were not absorbed")


def run_backend(name, be_cfg, rows, queries, sources, device):
    import jax
    import numpy as np
    from repro.engine import EngineConfig, EngineDriver, RetrievalEngine
    from repro.serve import serve_in_thread

    engine = RetrievalEngine(config=EngineConfig(
        d_emb=DIM, d_start=D_START, k0=K0, final_k=FINAL_K, buckets=(1,),
        capacity=CAPACITY, backend=be_cfg))
    t0 = time.perf_counter()
    for lo in range(0, N_LOAD, 32_768):
        engine.add_docs(rows[lo:min(lo + 32_768, N_LOAD)])
    t1 = time.perf_counter()
    engine.maybe_rebuild(force=True)
    jax.block_until_ready([x for x in jax.tree.leaves(engine.index_state.data)
                           if isinstance(x, jax.Array)])
    t2 = time.perf_counter()
    engine.warmup()                              # compiles the bucket
    t3 = time.perf_counter()
    check_kernel_path(name, engine)
    engine.add_docs(rows[N_LOAD:])               # absorbed at next dispatch
    driver = EngineDriver(engine, max_wait_ms=1.0).start()
    try:
        with serve_in_thread(engine, driver, require_tenant=False) as h:
            ids, secs, _ = serve_queries(h.url, queries)
        check_absorbed(name, engine)
        if engine.store.capacity != CAPACITY:
            raise AssertionError(f"{name}: store grew past {CAPACITY}")
    finally:
        driver.stop()
    res = {
        "backend": name,
        "ids": ids,
        "hit@1": float(np.mean(ids[:N_COPIES, 0] == sources)),
        "load_s": t1 - t0,
        "build_s": t2 - t1,
        "compile_s": t3 - t2,
        "p50_ms": float(np.percentile(secs, 50)) * 1e3,
        "peak_bytes": device.memory_stats()["peak_bytes_in_use"],
    }
    del engine, driver, h
    gc.collect()
    res["bytes_after_release"] = device.memory_stats()["bytes_in_use"]
    return res


def one_chip(args) -> int:
    os.environ["JAX_PLATFORMS"] = "tpu"          # no silent CPU fallback
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:                 # no TPU: no result line
        print(f"chip_smoke.py: JAX found no TPU: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: expected a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"[cut]    paper corpus 1,000,000 x {DIM} f32 (14.3 GB) does not "
        f"fit a 16 GB chip: {CAPACITY:,} rows ({N_LOAD:,} loaded, "
        f"{N_APPEND:,} appended), {CAPACITY * DIM * 4 / 1e9:.2f} GB")

    t0 = time.perf_counter()
    rows, queries, sources = make_data(CAPACITY, args.seed)
    stages = schedule_stages()
    log(f"[data]   {rows.shape} corpus + {len(queries)} queries in "
        f"{time.perf_counter() - t0:.1f}s; schedule {stages}")

    # peak_bytes is the process's high-water mark after each phase: the
    # references run last so that no backend's figure is theirs
    ok, results = True, []
    for name, cfg in backend_configs():
        try:
            results.append(run_backend(name, cfg, rows, queries, sources,
                                       dev))
        except Exception as e:                    # a failed phase fails all
            traceback.print_exc()
            log(f"[{name}] FAILED: {type(e).__name__}: {e}")
            ok = False
            gc.collect()
    try:
        t0 = time.perf_counter()
        exact_ids, prog_ids = references(rows, queries, stages)
        log(f"[ref]    exact + progressive references in "
            f"{time.perf_counter() - t0:.1f}s; progressive recall@10 vs "
            f"exact {overlap(prog_ids, exact_ids):.4f} "
            f"peak_bytes={dev.memory_stats()['peak_bytes_in_use']}")
    except Exception as e:
        traceback.print_exc()
        log(f"[ref] FAILED: {type(e).__name__}: {e}")
        results, ok = [], False

    for r in results:
        name = r["backend"]
        agree = overlap(r["ids"], prog_ids)
        r_ok = r["hit@1"] >= MIN_HIT1 and (name != "flat"
                                           or agree >= MIN_AGREE)
        ok &= r_ok
        log(f"[{name}] recall@10={overlap(r['ids'], exact_ids):.4f} "
            f"hit@1={r['hit@1']:.4f} agree_progressive={agree:.4f} "
            f"load={r['load_s']:.1f}s build={r['build_s']:.1f}s "
            f"compile={r['compile_s']:.1f}s p50={r['p50_ms']:.2f}ms "
            f"peak_bytes={r['peak_bytes']} "
            f"after_release={r['bytes_after_release']} "
            f"{'ok' if r_ok else 'FAILED'}")
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


# -- four chips ------------------------------------------------------------------
def build_snapshot(state_dir: str, seed: int) -> int:
    """CPU-pinned child of --chips 4: write the replicas' corpus as a
    snapshot (and the queries) into ``state_dir``."""
    import numpy as np
    from repro.engine import EngineConfig, RetrievalEngine

    rows, queries, sources = make_data(REPLICA_ROWS, seed)
    engine = RetrievalEngine(config=EngineConfig(
        d_emb=DIM, d_start=D_START, k0=K0, final_k=FINAL_K,
        capacity=REPLICA_ROWS))
    for lo in range(0, REPLICA_ROWS, 16_384):
        engine.add_docs(rows[lo:lo + 16_384])
    engine.enable_durability(state_dir)
    engine.save_snapshot()
    engine.wal.close()
    np.savez(os.path.join(state_dir, "queries.npz"), queries=queries,
             sources=sources)
    return 0


def free_ports(n: int):
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def wait_ready(url: str, proc, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(f"{url}: server exited with {proc.returncode}")
        status, _, _ = http(url, "/healthz?ready=1", timeout=5.0)
        if status == 200:
            return
        time.sleep(0.5)
    raise TimeoutError(f"{url} not ready after {timeout:.0f}s")


def replica_device(log_path: str):
    """The ``[device]`` boot line a replica printed: (platform, kind, n)."""
    with open(log_path) as f:
        for line in f:
            if line.startswith("[device]"):
                kv = dict(p.split("=", 1) for p in line.split()[1:])
                return kv["platform"], kv["kind"].replace("_", " "), int(
                    kv["count"])
    raise RuntimeError(f"no [device] line in {log_path}")


def replica_env(base, i: int):
    """Environment holding a replica process to chip ``i`` alone
    (libtpu's per-process chip visibility)."""
    port = 8476 + i
    return dict(base, JAX_PLATFORMS="tpu", TPU_VISIBLE_CHIPS=str(i),
                TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                TPU_PROCESS_BOUNDS="1,1,1", TPU_PROCESS_PORT=str(port),
                TPU_PROCESS_ADDRESSES=f"localhost:{port}")


def four_chips(args) -> int:
    import numpy as np                           # numpy only: no JAX here

    state = args.state_dir or tempfile.mkdtemp(prefix="replicas-")
    os.makedirs(state, exist_ok=True)
    logs = os.path.join(ROOT, "chiprun_out", "replicas")
    os.makedirs(logs, exist_ok=True)
    base = dict(os.environ, PYTHONPATH=SRC, PYTHONUNBUFFERED="1")
    cpu = dict(base, JAX_PLATFORMS="cpu")
    log(f"[cut]    replicas hold {REPLICA_ROWS:,} x {DIM} f32 "
        f"({REPLICA_ROWS * DIM * 4 / 1e9:.2f} GB) each, cut from the "
        f"paper's 1,000,000 rows")
    t0 = time.perf_counter()
    subprocess.run([sys.executable, os.path.abspath(__file__),
                    "--build-snapshot", state, "--seed", str(args.seed)],
                   env=cpu, check=True, timeout=900)
    qz = np.load(os.path.join(state, "queries.npz"))
    queries, sources = qz["queries"], qz["sources"]
    log(f"[state]  snapshot written by a CPU child in "
        f"{time.perf_counter() - t0:.1f}s")

    ports = free_ports(5)
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    serve = [sys.executable, "-m", "repro.launch.serve", "--serve-http",
             "--allow-anonymous", "--state-dir", state,
             "--d-emb", str(DIM), "--docs", str(REPLICA_ROWS),
             "--d-start", str(D_START), "--k0", str(K0),
             "--final-k", str(FINAL_K), "--buckets", "1"]
    procs, devs = [], []

    def spawn(tag, cmd, env):
        with open(os.path.join(logs, f"{tag}.log"), "w") as f:
            procs.append((tag, subprocess.Popen(
                cmd, env=env, stdout=f, stderr=subprocess.STDOUT)))
        return procs[-1][1]

    ok = False
    try:
        t0 = time.perf_counter()
        prim = spawn("primary", serve + ["--role=primary",
                                         "--port", str(ports[0])],
                     replica_env(base, 0))
        wait_ready(urls[0], prim, 600)
        followers = [spawn(f"follower{i}", serve + [
            "--role=follower", "--port", str(ports[i])], replica_env(base, i))
            for i in (1, 2, 3)]
        for url, p in zip(urls[1:4], followers):
            wait_ready(url, p, 600)
        router = spawn("router", serve + [
            "--role=router", "--port", str(ports[4]),
            "--replicas", ",".join(urls[:4])], cpu)
        wait_ready(urls[4], router, 120)
        log(f"[boot]   primary + 3 followers + router ready in "
            f"{time.perf_counter() - t0:.1f}s")
        devs = [replica_device(os.path.join(logs, f"{t}.log"))
                for t in ("primary", "follower1", "follower2", "follower3")]
        log(f"[device] replicas: {devs}")

        # warm every replica (first search compiles), then measure
        for u in urls[:4]:
            serve_queries(u, queries[:1])
        prim_ids, prim_s, _ = serve_queries(urls[0], queries)
        per_replica = [prim_ids] + [serve_queries(u, queries)[0]
                                    for u in urls[1:4]]
        rt_ids, rt_s, served = serve_queries(urls[4], queries)
        same = [bool((r == prim_ids).all()) for r in per_replica]
        hit1 = float(np.mean(prim_ids[:N_COPIES, 0] == sources))
        spread = {u: served.count(u) for u in urls[:4]}
        ok = (all(same) and bool((rt_ids == prim_ids).all())
              and hit1 >= MIN_HIT1
              and all(d[0] == "tpu" and d[2] == 1 for d in devs)
              and len({d[1] for d in devs}) == 1)
        log(f"[alone]  primary alone: hit@1={hit1:.4f} "
            f"p50={np.percentile(prim_s, 50) * 1e3:.2f}ms")
        log(f"[router] through the router: same ids as primary alone="
            f"{bool((rt_ids == prim_ids).all())} "
            f"p50={np.percentile(rt_s, 50) * 1e3:.2f}ms served_by={spread}")
        log(f"[match]  each replica == primary: {same}")
    finally:
        for tag, p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for tag, p in procs:
            try:
                p.wait(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        if not args.state_dir:
            shutil.rmtree(state, ignore_errors=True)
    platform, kind, _ = devs[0] if devs else ("unknown", "unknown", 0)
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}),
        flush=True)
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: every backend on one chip; 4: four one-chip "
                         "replicas behind the router")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--state-dir", default="",
                    help="--chips 4: shared replica state directory "
                         "(default: a fresh temporary one)")
    ap.add_argument("--build-snapshot", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke.py: no repro package under {SRC}; run it from "
              f"the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    if args.build_snapshot:
        return build_snapshot(args.build_snapshot, args.seed)
    if args.chips == 4:
        return four_chips(args)
    return one_chip(args)


if __name__ == "__main__":
    sys.exit(main())
