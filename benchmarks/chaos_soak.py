"""Chaos soak: the fault-tolerance layer exercised end to end.

Five phases, all driven by the deterministic `repro.engine.faults` harness
or explicit file surgery (never racing real hardware faults), recorded to
``results/BENCH_chaos.json``:

1. **sigkill durability** — a child process acknowledges WAL-backed
   mutations and is SIGKILLed mid-churn; the parent recovers the state
   directory and must hold every acknowledged add, resurrect no tombstone,
   and serve the recovered corpus.  Records recovery + replay timings.
2. **torn checkpoint** — the newest snapshot's manifest is corrupted on
   disk; recovery must detect the damage via checksums, fall back to the
   previous snapshot, and replay the WAL tail so no acknowledged mutation
   is lost.
3. **crash storm** — a supervised driver whose dispatches crash with
   probability p; the supervisor must restart the thread (capped backoff)
   and the service must keep answering between crashes and after the storm.
4. **rebuild retry** — background index rebuilds fail transiently; the
   engine must keep serving the old index, retry, and adopt the rebuilt
   index once a build succeeds.
5. **poison isolation** — a batch carrying poison requests; bisection must
   quarantine exactly the offenders while every clean request is served.
6. **replica kill** — a primary + two WAL-tailing followers behind an
   in-process `ReplicaRouter`; one follower is SIGKILLed under open-loop
   search load.  Zero non-429 search failures are tolerated, the router
   must open the dead replica's breaker within the probe window, and the
   restarted follower must rejoin via snapshot + WAL catch-up and serve
   again.  Includes a read-your-writes sub-check (a ``min_seq`` token from
   a mutation is honoured on every replica) and a deterministic
   ``replica_apply`` fault-injection sub-check.

This is a harness for WAL and replication semantics, not a device path.
It runs on the CPU, and so does every child it starts: ``JAX_PLATFORMS=cpu``
is set before the parent's first JAX import and in each child's
environment.  On a host with a chip, the parent would otherwise hold the
chip and its server children would fail on the TPU runtime's lock.

Exit status is non-zero if any check fails.  ``--smoke`` (CI) shrinks the
corpus and the storm but enforces every check — all six phases are
deterministic, so nothing is skipped:

    PYTHONPATH=src python -m benchmarks.chaos_soak --smoke
    PYTHONPATH=src python -m benchmarks.chaos_soak
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

WAIT = 60.0
# every child process runs on the CPU too (see the module docstring)
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")
FAST_FT = dict(heartbeat_timeout_s=0.2, backoff_initial_s=0.01,
               backoff_max_s=0.05)


def make_engine(args, *, n_docs=None, fault=None, **kw):
    from repro.engine import RetrievalEngine

    kw.setdefault("d_start", 8)
    kw.setdefault("k0", 16)
    kw.setdefault("buckets", (1, 2, 4, 8))
    kw.setdefault("capacity", max(args.docs * 2, 128))
    kw.setdefault("block_n", 64)
    eng = RetrievalEngine(args.dim, fault=fault, **kw)
    rng = np.random.default_rng(args.seed)
    n = args.docs if n_docs is None else n_docs
    db = rng.normal(size=(max(n, 1), args.dim)).astype(np.float32)
    if n:
        eng.add_docs(db)
    return eng, db


def wait_until(pred, timeout=WAIT, msg="condition"):
    deadline = time.perf_counter() + timeout
    while not pred():
        if time.perf_counter() >= deadline:
            raise TimeoutError(f"timed out waiting: {msg}")
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# phase 1: SIGKILL a churning child, recover in-process
# ---------------------------------------------------------------------------
CHILD = r"""
import os, sys, numpy as np
sys.path.insert(0, {src!r})
from repro.engine import RetrievalEngine

eng = RetrievalEngine({d}, d_start=8, k0=16, buckets=(1,), capacity=4096,
                      block_n=64)
eng.enable_durability({state!r})
rng = np.random.default_rng(7)
ack = open(os.path.join({state!r}, "acked.log"), "a")
os.write(1, b"ready\n")
i = 0
while True:
    vecs = rng.normal(size=(2, {d})).astype(np.float32) + i
    ids = eng.add_docs(vecs)
    if i % 5 == 4:
        eng.delete_docs(ids[:1])
        note = f"del {{ids[0]}}\n"
    else:
        note = ""
    if i == {snap_at}:
        eng.save_snapshot()
    # ack AFTER the engine returned: the WAL record is already fsync'd
    ack.write(f"add {{ids[0]}} {{ids[1]}}\n" + note)
    ack.flush(); os.fsync(ack.fileno())
    i += 1
"""


def phase_sigkill(args, state: str) -> dict:
    from repro.engine import RetrievalEngine

    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    code = CHILD.format(src=src, d=args.dim, state=state,
                        snap_at=args.churn_snapshot_at)
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, env=CPU_ENV)
    try:
        assert proc.stdout.readline().strip() == b"ready"
        time.sleep(args.churn_s)
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=WAIT)
    finally:
        if proc.poll() is None:
            proc.kill()

    acked_adds, acked_dels = set(), set()
    with open(os.path.join(state, "acked.log")) as f:
        for line in f:
            kind, *ids = line.split()
            if kind == "add":
                acked_adds.update(int(x) for x in ids)
            else:
                acked_dels.add(int(ids[0]))

    eng = RetrievalEngine(args.dim, d_start=8, k0=16, buckets=(1,),
                          capacity=4096, block_n=64)
    t0 = time.perf_counter()
    report = eng.recover(state)
    recover_s = time.perf_counter() - t0
    live = acked_adds - acked_dels
    lost = [i for i in sorted(live) if not eng.store.is_live(i)]
    resurrected = [i for i in sorted(acked_dels) if eng.store.is_live(i)]
    some = sorted(live)[:4]
    q = np.stack([np.asarray(eng.store.db[i]) for i in some])
    _, idx = eng.search(q)
    serves = bool(np.array_equal(idx[:, 0], some))
    eng.wal.close()
    return {
        "acked_adds": len(acked_adds),
        "acked_deletes": len(acked_dels),
        "lost": lost,
        "resurrected": resurrected,
        "serves_recovered_docs": serves,
        "recover_wall_s": recover_s,
        "report": report,
    }


# ---------------------------------------------------------------------------
# phase 2: corrupt the newest snapshot, fall back + replay
# ---------------------------------------------------------------------------
def phase_torn_checkpoint(args, state: str) -> dict:
    from repro.engine import RetrievalEngine

    eng, _ = make_engine(args, n_docs=0)
    eng.enable_durability(state)
    rng = np.random.default_rng(args.seed + 1)
    a = rng.normal(size=(args.docs, args.dim)).astype(np.float32)
    eng.add_docs(a)
    eng.save_snapshot()
    b = rng.normal(size=(16, args.dim)).astype(np.float32)
    eng.add_docs(b)
    eng.save_snapshot()                    # newest — about to be torn
    c = rng.normal(size=(8, args.dim)).astype(np.float32)
    ids_c = eng.add_docs(c)                # WAL-only tail
    eng.wal.close()

    snaps = sorted(d for d in os.listdir(state) if d.startswith("step_"))
    manifest = os.path.join(state, snaps[-1], "manifest.msgpack")
    with open(manifest, "wb") as f:
        f.write(b"\xc1 torn mid-write")

    eng2, _ = make_engine(args, n_docs=0)
    report = eng2.recover(state)
    _, idx = eng2.search(c[:1])
    eng2.wal.close()
    return {
        "report": report,
        "tail_doc_served": bool(idx[0, 0] == ids_c[0]),
        "n_docs_recovered": eng2.n_docs,
        "n_docs_expected": args.docs + 16 + 8,
    }


# ---------------------------------------------------------------------------
# phase 3: probabilistic crash storm under supervision
# ---------------------------------------------------------------------------
def phase_crash_storm(args) -> dict:
    from repro.engine import (DriverStopped, EngineDriver,
                              FaultToleranceConfig, Supervisor)

    eng, db = make_engine(args, fault=FaultToleranceConfig(
        inject=f"dispatch:crash@p={args.crash_p}",
        inject_seed=args.seed, max_restarts=10 ** 6, **FAST_FT))
    driver = EngineDriver(eng, max_wait_ms=0.0, max_queue=256)
    driver.start(supervised=True)
    sup = Supervisor(driver).start()
    served = failed = 0
    t0 = time.perf_counter()
    try:
        for i in range(args.storm_requests):
            try:
                res = driver.retrieve(db[i % len(db)], timeout=WAIT)
                served += 1
                assert res.doc_ids[0] == i % len(db)
            except DriverStopped:
                failed += 1               # our chunk crashed; storm goes on
                wait_until(lambda: driver.health()["thread_alive"],
                           msg="supervisor restart mid-storm")
        # calm after the storm: disarm and require clean service
        eng.faults = type(eng.faults)()
        wait_until(lambda: driver.health()["thread_alive"],
                   msg="driver alive post-storm")
        final = driver.retrieve(db[0], timeout=WAIT)
        survived = bool(final.doc_ids[0] == 0)
    finally:
        sup.stop()
        driver.stop()
    return {
        "requests": args.storm_requests,
        "served": served,
        "crash_failed": failed,
        "crashes": driver.stats.n_driver_crashes,
        "restarts": driver.stats.n_restarts,
        "survived_storm": survived,
        "wall_s": time.perf_counter() - t0,
        "supervisor": sup.summary(),
    }


# ---------------------------------------------------------------------------
# phase 4: transient background-rebuild failures retried to adoption
# ---------------------------------------------------------------------------
def phase_rebuild_retry(args) -> dict:
    from repro.engine import FaultPlan, FaultToleranceConfig, RetrievalEngine

    rng = np.random.default_rng(args.seed + 2)
    eng = RetrievalEngine(
        args.dim, d_start=8, k0=16, buckets=(1, 2), capacity=args.docs * 4,
        block_n=64, backend="quantized",
        backend_opts={"min_rebuild_rows": 8}, rebuild_mode="background",
        fault=FaultToleranceConfig(rebuild_retries=5))
    db = rng.normal(size=(args.docs, args.dim)).astype(np.float32)
    eng.add_docs(db)
    eng.search(db[:1])                     # warm (sync) build, clean
    eng.faults = FaultPlan.parse("rebuild:error@first=2")
    eng.add_docs(rng.normal(
        size=(args.docs, args.dim)).astype(np.float32))
    deadline = time.perf_counter() + WAIT
    while eng.stats.n_rebuilds < 2:
        eng.maybe_rebuild()
        if time.perf_counter() >= deadline:
            break
        time.sleep(0.01)
    _, idx = eng.search(db[:4])
    return {
        "rebuilds": eng.stats.n_rebuilds,
        "rebuild_failures": eng.stats.n_rebuild_failures,
        "adopted_after_retries": eng.stats.n_rebuilds >= 2,
        "serves_after_adoption": bool(
            np.array_equal(idx[:, 0], np.arange(4))),
    }


# ---------------------------------------------------------------------------
# phase 5: poison isolation by batch bisection
# ---------------------------------------------------------------------------
def phase_poison(args) -> dict:
    from repro.engine import (EngineDriver, FaultToleranceConfig,
                              RequestFailed)

    eng, db = make_engine(args, fault=FaultToleranceConfig(
        inject="dispatch:poison@v=777.0"))
    n = min(16, len(db))
    queries = [db[i].copy() for i in range(n)]
    poison_at = {1, n - 2}
    for i in poison_at:
        queries[i][0] = 777.0
    driver = EngineDriver(eng, max_wait_ms=60_000)   # unstarted: inline
    futs = [driver.submit(q) for q in queries]
    driver.stop(drain=True)
    isolated, clean_ok = 0, 0
    for i, f in enumerate(futs):
        exc = f.exception(0)
        if i in poison_at:
            isolated += isinstance(exc, RequestFailed)
        elif exc is None and f.result(0).doc_ids[0] == i:
            clean_ok += 1
    return {
        "batch": n,
        "poisoned": len(poison_at),
        "isolated": isolated,
        "clean_served": clean_ok,
        "quarantined": driver.stats.n_quarantined,
        "bisections": driver.stats.n_bisections,
    }


# ---------------------------------------------------------------------------
# phase 6: SIGKILL a replica under load; failover, rejoin, read-your-writes
# ---------------------------------------------------------------------------
def _free_ports(n: int):
    import socket

    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _spawn_server(role: str, state: str, port: int, dim: int, log_path: str,
                  snapshot_every_s: float = 0.0) -> subprocess.Popen:
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    cmd = [sys.executable, "-m", "repro.launch.serve", "--serve-http",
           f"--role={role}", "--state-dir", state, "--port", str(port),
           "--allow-anonymous", "--docs", "0", "--d-emb", str(dim)]
    if snapshot_every_s > 0:
        cmd += ["--snapshot-every-s", str(snapshot_every_s)]
    env = dict(CPU_ENV, PYTHONPATH=src)
    log = open(log_path, "ab")
    return subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                            env=env)


def phase_replica_kill(args, state: str) -> dict:
    from repro.serve import ReplicaRouter, http_call

    os.makedirs(state, exist_ok=True)
    p_prim, p_f1, p_f2 = _free_ports(3)
    urls = [f"http://127.0.0.1:{p}" for p in (p_prim, p_f1, p_f2)]
    procs: dict = {}

    def boot(name, role, port):
        procs[name] = _spawn_server(
            role, state, port, args.dim,
            os.path.join(state, f"{name}.log"),
            snapshot_every_s=1.0 if role == "primary" else 0.0)

    def wait_ready_url(url, timeout=WAIT):
        wait_until(lambda: http_call(url, "/healthz?ready=1",
                                     timeout=2.0)[0] == 200,
                   timeout=timeout, msg=f"{url} ready")

    router = None
    try:
        boot("primary", "primary", p_prim)
        wait_ready_url(urls[0])
        boot("f1", "follower", p_f1)
        boot("f2", "follower", p_f2)
        wait_ready_url(urls[1])
        wait_ready_url(urls[2])

        router = ReplicaRouter(urls, probe_interval_s=0.1,
                               failure_threshold=2, breaker_open_s=0.2,
                               request_timeout_s=WAIT).start()
        router.wait_ready(3, timeout=WAIT)

        rng = np.random.default_rng(args.seed + 6)
        docs = rng.normal(size=(args.docs, args.dim)).astype(np.float32)
        status, payload, _ = router.mutate("/v1/docs", {
            "vectors": docs.tolist(), "tenant": "chaos"})
        assert status == 200, f"seed add failed: {status} {payload}"

        # read-your-writes: a fresh mutation's seq token must be honoured
        # on EVERY replica — no replica may serve a pre-mutation view
        marker = (rng.normal(size=(1, args.dim)) + 50.0).astype(np.float32)
        status, payload, _ = router.mutate("/v1/docs", {
            "vectors": marker.tolist(), "tenant": "chaos"})
        assert status == 200, f"marker add failed: {status} {payload}"
        marker_id, marker_seq = payload["ids"][0], payload["seq"]
        ryw = {}
        for url in urls:
            s, p = http_call(url, "/v1/search", {
                "query": marker[0].tolist(), "tenant": "chaos", "k": 1,
                "min_seq": marker_seq, "deadline_ms": 30_000}, timeout=WAIT)
            ryw[url] = bool(s == 200 and p["ids"][0] == marker_id)

        # open-loop load; SIGKILL one follower a third of the way in
        n_req = args.replica_requests
        queries = rng.normal(size=(n_req, args.dim)).astype(np.float32)
        codes = []
        kill_at = n_req // 3
        t_kill = t_detect = None
        f1_ep = next(ep for ep in router.replicas if ep.url == urls[1])
        for i in range(n_req):
            if i == kill_at:
                os.kill(procs["f1"].pid, signal.SIGKILL)
                procs["f1"].wait(timeout=WAIT)
                t_kill = time.perf_counter()
            s, _, _ = router.search({
                "query": queries[i].tolist(), "tenant": "chaos", "k": 1,
                "deadline_ms": 30_000})
            codes.append(s)
            if t_kill is not None and t_detect is None \
                    and not (f1_ep.alive and f1_ep.breaker.allow()):
                t_detect = time.perf_counter()
        if t_detect is None and not (f1_ep.alive and f1_ep.breaker.allow()):
            t_detect = time.perf_counter()
        bad = [c for c in codes if c not in (200, 429)]
        detect_s = (t_detect - t_kill) if t_detect else None

        # rejoin: wait for a primary snapshot so the restart exercises the
        # snapshot + WAL-tail bootstrap path, then bring f1 back
        wait_until(lambda: any(d.startswith("step_")
                               for d in os.listdir(state)),
                   msg="primary snapshot on disk")
        boot("f1", "follower", p_f1)
        wait_ready_url(urls[1])
        s, deep = http_call(urls[1], "/healthz?deep=1", timeout=WAIT)
        repl = (deep.get("deep") or {}).get("replication") or {}
        boot_report = repl.get("last_bootstrap") or {}
        prim_seq = http_call(urls[0], "/healthz",
                             timeout=WAIT)[1]["applied_seq"]
        wait_until(lambda: http_call(
            urls[1], "/healthz",
            timeout=2.0)[1].get("applied_seq", -1) >= prim_seq,
            msg="restarted follower catches up")
        s, p = http_call(urls[1], "/v1/search", {
            "query": marker[0].tolist(), "tenant": "chaos", "k": 1,
            "min_seq": marker_seq, "deadline_ms": 30_000}, timeout=WAIT)
        rejoined_serves = bool(s == 200 and p["ids"][0] == marker_id)

        return {
            "requests": n_req,
            "codes": {str(c): codes.count(c) for c in sorted(set(codes))},
            "non_retryable_failures": len(bad),
            "failover_detect_s": detect_s,
            "read_your_writes": ryw,
            "rejoin_bootstrap_snapshot": boot_report.get("snapshot_step"),
            "rejoined_serves_min_seq": rejoined_serves,
            "router": router.status(),
        }
    finally:
        if router is not None:
            router.stop()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=WAIT)


def phase_replica_faults(args) -> dict:
    """In-process ``wal_ship``/``replica_apply`` fault-site sub-check: the
    applier counts and retries injected faults, then converges."""
    import tempfile

    from repro.engine import FaultPlan, ReplicaApplier, RetrievalEngine

    rng = np.random.default_rng(args.seed + 7)
    with tempfile.TemporaryDirectory() as td:
        prim = RetrievalEngine(args.dim, d_start=8, k0=16, buckets=(1,),
                               capacity=1024, block_n=64)
        prim.enable_durability(td)
        prim.add_docs(rng.normal(size=(32, args.dim)).astype(np.float32))
        want = prim.wal.last_seq

        foll = RetrievalEngine(args.dim, d_start=8, k0=16, buckets=(1,),
                               capacity=1024, block_n=64)
        foll.faults = FaultPlan.parse(
            "wal_ship:error@first=1;replica_apply:error@first=2",
            seed=args.seed)
        applier = ReplicaApplier(foll, td, poll_s=0.01)
        applier.bootstrap()
        applier.start()
        try:
            wait_until(lambda: applier.applied_seq >= want,
                       msg="applier converges through injected faults")
        finally:
            applier.stop()
            prim.wal.close()
        st = applier.status()
        return {
            "applied_seq": st["applied_seq"],
            "want_seq": want,
            "n_poll_errors": st["n_poll_errors"],
            "n_apply_errors": st["n_apply_errors"],
            "n_docs": foll.n_docs,
        }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--docs", type=int, default=512)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--churn-s", type=float, default=2.0,
                    help="how long the SIGKILL child churns mutations")
    ap.add_argument("--churn-snapshot-at", type=int, default=40,
                    help="child iteration that cuts a mid-churn snapshot")
    ap.add_argument("--storm-requests", type=int, default=200)
    ap.add_argument("--crash-p", type=float, default=0.2)
    ap.add_argument("--replica-requests", type=int, default=120,
                    help="open-loop searches driven through the router "
                         "while a replica is SIGKILLed")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run; every check still enforced")
    args = ap.parse_args()

    if args.smoke:
        args.docs, args.dim = 128, 32
        args.churn_s, args.churn_snapshot_at = 0.6, 15
        args.storm_requests = 60
        args.replica_requests = 60

    import tempfile

    print(f"# chaos_soak docs={args.docs} dim={args.dim} "
          f"churn_s={args.churn_s} storm={args.storm_requests} "
          f"smoke={args.smoke}")

    with tempfile.TemporaryDirectory() as td:
        sigkill = phase_sigkill(args, os.path.join(td, "sigkill"))
    print(f"sigkill: acked={sigkill['acked_adds']} lost={sigkill['lost']} "
          f"recover_s={sigkill['recover_wall_s']:.3f} "
          f"replayed={sigkill['report']['replayed']}")

    with tempfile.TemporaryDirectory() as td:
        torn = phase_torn_checkpoint(args, os.path.join(td, "torn"))
    print(f"torn: fallbacks={torn['report']['fallbacks']} "
          f"replayed={torn['report']['replayed']} "
          f"docs={torn['n_docs_recovered']}/{torn['n_docs_expected']}")

    storm = phase_crash_storm(args)
    print(f"storm: served={storm['served']}/{storm['requests']} "
          f"crashes={storm['crashes']} restarts={storm['restarts']} "
          f"wall_s={storm['wall_s']:.2f}")

    rebuild = phase_rebuild_retry(args)
    print(f"rebuild: failures={rebuild['rebuild_failures']} "
          f"adopted={rebuild['adopted_after_retries']}")

    poison = phase_poison(args)
    print(f"poison: isolated={poison['isolated']}/{poison['poisoned']} "
          f"clean={poison['clean_served']}/{poison['batch'] - 2}")

    with tempfile.TemporaryDirectory() as td:
        replica = phase_replica_kill(args, os.path.join(td, "replica"))
    print(f"replica: codes={replica['codes']} "
          f"detect_s={replica['failover_detect_s']} "
          f"ryw={sum(replica['read_your_writes'].values())}/"
          f"{len(replica['read_your_writes'])} "
          f"rejoined={replica['rejoined_serves_min_seq']}")

    rfaults = phase_replica_faults(args)
    print(f"replica-faults: poll_errors={rfaults['n_poll_errors']} "
          f"apply_errors={rfaults['n_apply_errors']} "
          f"applied={rfaults['applied_seq']}/{rfaults['want_seq']}")

    checks = {
        # 1: every fsync-acked mutation survives SIGKILL
        "sigkill_child_did_real_work": sigkill["acked_adds"] > 4,
        "sigkill_no_acked_loss": not sigkill["lost"],
        "sigkill_no_resurrection": not sigkill["resurrected"],
        "sigkill_recovered_corpus_serves":
            sigkill["serves_recovered_docs"],
        # 2: checksum catches the torn snapshot; fallback + replay is exact
        "torn_fallback_taken": torn["report"]["fallbacks"] >= 1,
        "torn_status_ok": torn["report"]["status"] == "ok",
        "torn_tail_replayed": torn["report"]["replayed"] > 0
            and torn["tail_doc_served"],
        "torn_no_doc_lost":
            torn["n_docs_recovered"] == torn["n_docs_expected"],
        # 3: the storm is survived, not merely endured
        "storm_crashed_and_restarted": storm["crashes"] >= 1
            and storm["restarts"] >= 1,
        "storm_service_continued": storm["served"] > 0,
        "storm_survived": storm["survived_storm"],
        # 4: rebuild retries converge and the new index serves
        "rebuild_retried_to_adoption": rebuild["adopted_after_retries"]
            and rebuild["rebuild_failures"] == 2,
        "rebuild_serves": rebuild["serves_after_adoption"],
        # 5: exactly the poisons quarantined, every clean request served
        "poison_exact_isolation":
            poison["isolated"] == poison["poisoned"]
            and poison["quarantined"] == poison["poisoned"],
        "poison_clean_unharmed":
            poison["clean_served"] == poison["batch"] - poison["poisoned"],
        # 6: a SIGKILLed replica never surfaces as a non-429 failure; the
        #    breaker opens within the probe window; the restarted follower
        #    rejoins (snapshot + WAL tail) and honours old min_seq tokens
        "replica_zero_nonretryable_failures":
            replica["non_retryable_failures"] == 0,
        "replica_failover_within_probe_window":
            replica["failover_detect_s"] is not None
            and replica["failover_detect_s"] < 5.0,
        "replica_read_your_writes":
            all(replica["read_your_writes"].values()),
        "replica_rejoined_from_snapshot":
            replica["rejoin_bootstrap_snapshot"] is not None,
        "replica_rejoined_serves": replica["rejoined_serves_min_seq"],
        "replica_fault_sites_retried":
            rfaults["n_poll_errors"] >= 1 and rfaults["n_apply_errors"] >= 1
            and rfaults["applied_seq"] == rfaults["want_seq"],
    }

    record = {
        "bench": "chaos_soak",
        "smoke": args.smoke,
        "config": {
            "docs": args.docs, "dim": args.dim, "churn_s": args.churn_s,
            "storm_requests": args.storm_requests, "crash_p": args.crash_p,
            "replica_requests": args.replica_requests, "seed": args.seed,
        },
        "sigkill": sigkill,
        "torn_checkpoint": torn,
        "crash_storm": storm,
        "rebuild_retry": rebuild,
        "poison": poison,
        "replica_kill": replica,
        "replica_faults": rfaults,
        "checks": checks,
    }

    out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "BENCH_chaos.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {os.path.normpath(out)}")

    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        print(f"FAILED checks: {failed}", file=sys.stderr)
        sys.exit(1)
    print("all checks passed")


if __name__ == "__main__":
    main()
