"""Retrieval-engine throughput benchmark: caller-paced bucket ladders and the
async driver's deadline/concurrency trade-off.

Two measurement modes, two JSON records:

* **Ladder sweep** (caller-paced, as in PR 1): replays single-query requests
  through ``RetrievalEngine``'s queue for several bucket configurations and
  reports per-config QPS / p50 / p95 / padding waste
  -> ``results/BENCH_engine.json``.
* **Driver sweep** (async serving path): N concurrent client threads submit
  through ``EngineDriver`` for each (``max_wait_ms``, clients) combination —
  QPS vs latency percentiles as the deadline knob and offered concurrency
  move -> ``results/BENCH_driver.json``.

    PYTHONPATH=src python -m benchmarks.engine_throughput [--smoke]
    PYTHONPATH=src python -m benchmarks.engine_throughput \
        --docs 20000 --dim 256 --requests 512 --configs "1|8|32|1,2,4,8,16,32" \
        --driver-wait-ms 0,2,8 --driver-clients 1,8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def make_engine(db, buckets, *, d_start, k0, capacity):
    from repro.engine import RetrievalEngine

    eng = RetrievalEngine(
        db.shape[1], d_start=d_start, k0=k0,
        buckets=buckets, capacity=capacity,
    )
    eng.add_docs(db)
    # Warm every bucket so steady-state numbers exclude XLA compiles.
    eng.warmup()
    return eng


def latency_summary(eng):
    """p50/p95 through the shared ``repro.obs`` histogram buckets — the
    same resolution a ``/metrics`` scrape of the live engine reports, so
    BENCH records and online percentiles are directly comparable."""
    from repro.obs import summarize_latency

    lat = summarize_latency(eng.stats.latency_ms)
    queue = summarize_latency(eng.stats.queue_ms, pcts=(50.0,))
    return lat["p50"], lat["p95"], queue["p50"]


def run_config(db, queries, buckets, *, d_start, k0, capacity):
    eng = make_engine(db, buckets, d_start=d_start, k0=k0, capacity=capacity)

    t0 = time.perf_counter()
    rids = [eng.submit(q) for q in queries]
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    for rid in rids:
        assert eng.poll(rid) is not None
    s = eng.stats.summary()
    p50, p95, q50 = latency_summary(eng)
    return {
        "buckets": list(buckets),
        "requests": len(queries),
        "qps": len(queries) / wall,
        "wall_s": wall,
        "latency_ms_p50": p50,
        "latency_ms_p95": p95,
        "queue_ms_p50": q50,
        "n_batches": s["n_batches"],
        "n_padded_slots": s["n_padded_slots"],
        "n_compiles_steady": s["n_compiles"],   # 0 expected after warmup
    }


def run_driver_config(db, queries, buckets, *, max_wait_ms, clients,
                      d_start, k0, capacity, timeout=300.0):
    """One driver-path measurement: ``clients`` threads racing submits."""
    from repro.engine import EngineDriver
    from repro.launch.serve import run_clients

    eng = make_engine(db, buckets, d_start=d_start, k0=k0, capacity=capacity)
    driver = EngineDriver(eng, max_wait_ms=max_wait_ms,
                          max_queue=max(len(queries), 1)).start()
    try:
        _, wall = run_clients(driver, queries, clients, qps=0.0,
                              timeout=timeout)
    finally:
        driver.stop()

    s = eng.stats.summary()
    ds = driver.stats.summary()
    p50, p95, q50 = latency_summary(eng)
    return {
        "max_wait_ms": max_wait_ms,
        "clients": clients,
        "buckets": list(buckets),
        "requests": len(queries),
        "qps": len(queries) / wall,
        "wall_s": wall,
        "latency_ms_p50": p50,
        "latency_ms_p95": p95,
        "queue_ms_p50": q50,
        "n_batches": s["n_batches"],
        "n_padded_slots": s["n_padded_slots"],
        "n_flush_full": ds["n_flush_full"],
        "n_flush_deadline": ds["n_flush_deadline"],
        "queue_peak": ds["queue_peak"],
    }


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--docs", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--d-start", type=int, default=32)
    ap.add_argument("--k0", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--configs", type=str,
                    default="1|8|32|1,2,4,8,16,32",
                    help="'|'-separated bucket ladders, each comma-separated")
    ap.add_argument("--driver-buckets", type=str, default="1,2,4,8,16,32",
                    help="bucket ladder for the driver sweep")
    ap.add_argument("--driver-wait-ms", type=str, default="0,2,8",
                    help="comma-separated max_wait_ms values to sweep")
    ap.add_argument("--driver-clients", type=str, default="1,8",
                    help="comma-separated concurrent-client counts to sweep")
    ap.add_argument("--out", type=str, default=None,
                    help="output JSON path (default results/BENCH_engine.json;"
                         " driver records go next to it as BENCH_driver.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast run for CI (overrides sizes)")
    args = ap.parse_args()

    if args.smoke:
        args.docs, args.dim, args.requests = 512, 64, 48
        args.d_start, args.k0 = 8, 16
        args.configs = "4|1,2,4,8"
        args.driver_buckets = "1,2,4,8"
        args.driver_wait_ms = "0,4"
        args.driver_clients = "4"

    from repro.rag import make_corpus

    corpus = make_corpus(n_docs=args.docs, dim=args.dim,
                         n_queries=args.requests, seed=args.seed)
    configs = [tuple(int(x) for x in c.split(","))
               for c in args.configs.split("|")]

    print(f"# engine_throughput docs={args.docs} dim={args.dim} "
          f"requests={args.requests} smoke={args.smoke}")
    print("buckets,qps,p50_ms,p95_ms,batches,padded_slots")
    records = []
    for buckets in configs:
        rec = run_config(
            corpus.db, corpus.queries, buckets,
            d_start=args.d_start, k0=args.k0, capacity=args.docs,
        )
        records.append(rec)
        print(f"\"{','.join(map(str, buckets))}\","
              f"{rec['qps']:.1f},{rec['latency_ms_p50']:.2f},"
              f"{rec['latency_ms_p95']:.2f},{rec['n_batches']},"
              f"{rec['n_padded_slots']}")

    driver_buckets = tuple(
        int(x) for x in args.driver_buckets.split(","))
    wait_values = [float(x) for x in args.driver_wait_ms.split(",")]
    client_values = [int(x) for x in args.driver_clients.split(",")]
    print("# driver sweep (async path)")
    print("max_wait_ms,clients,qps,p50_ms,p95_ms,batches,"
          "flush_full,flush_deadline")
    driver_records = []
    for clients in client_values:
        for wait_ms in wait_values:
            rec = run_driver_config(
                corpus.db, corpus.queries, driver_buckets,
                max_wait_ms=wait_ms, clients=min(clients, args.requests),
                d_start=args.d_start, k0=args.k0, capacity=args.docs,
            )
            driver_records.append(rec)
            print(f"{wait_ms:g},{rec['clients']},{rec['qps']:.1f},"
                  f"{rec['latency_ms_p50']:.2f},{rec['latency_ms_p95']:.2f},"
                  f"{rec['n_batches']},{rec['n_flush_full']},"
                  f"{rec['n_flush_deadline']}")

    out_path = args.out or os.path.join(
        os.path.dirname(__file__), "..", "results", "BENCH_engine.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    common = {
        "docs": args.docs,
        "dim": args.dim,
        "requests": args.requests,
        "smoke": args.smoke,
    }
    with open(out_path, "w") as f:
        json.dump({"benchmark": "engine_throughput", **common,
                   "records": records}, f, indent=2)
    print(f"# wrote {os.path.normpath(out_path)}")

    driver_path = os.path.join(os.path.dirname(out_path),
                               "BENCH_driver.json")
    with open(driver_path, "w") as f:
        json.dump({"benchmark": "engine_driver", **common,
                   "buckets": list(driver_buckets),
                   "records": driver_records}, f, indent=2)
    print(f"# wrote {os.path.normpath(driver_path)}")


if __name__ == "__main__":
    main()
