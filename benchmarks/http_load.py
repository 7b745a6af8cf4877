"""Open-loop HTTP load benchmark for the `repro.serve` front-end.

Boots the HTTP server over a fresh engine (or targets a running one with
``--url``), seeds ``--tenants`` isolated namespaces with metadata-tagged
documents, then drives them concurrently:

* per tenant, ``--clients`` open-loop threads submit ``--requests``
  searches (half of them metadata-filtered) and record status + latency;
* per tenant, one churn thread adds and deletes documents over HTTP the
  whole time, so the measurement covers the mutation path racing the
  search path.

Every returned doc id is checked against the requesting tenant's own
id universe after the run — cross-tenant leakage is a hard failure, as is
any response outside {2xx, 429} (429 is the admission-control contract,
not an error).  The run also exercises the observability surface:
``/metrics`` is scraped mid-run (the exposition must parse) and again at
quiescence (every histogram's ``_count`` must agree with its paired
counter), and every sampled 200 search response must carry a queue-wait
span.  Writes per-tenant QPS / p50 / p95 (computed through the shared
``repro.obs`` histogram buckets, so they are directly comparable to
``/metrics`` percentiles) and the global summary to
``results/BENCH_http.json`` alongside ``BENCH_driver.json``.

    PYTHONPATH=src python -m benchmarks.http_load --smoke
    PYTHONPATH=src python -m benchmarks.http_load \
        --tenants 4 --docs 2000 --requests 256 --clients 8 --backend ivf
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import http_json
from repro.obs import parse_prometheus, summarize_latency

N_SHARDS = 4                       # metadata cardinality for filtered queries


def scrape_metrics(url, timeout=30.0):
    """GET /metrics and parse the exposition (raises on malformed text)."""
    with urllib.request.urlopen(url.rstrip("/") + "/metrics",
                                timeout=timeout) as resp:
        text = resp.read().decode()
    return parse_prometheus(text), text


def check_histogram_counter_pairs(metrics):
    """Every histogram ``_count`` must agree with its paired counter.

    Only meaningful at quiescence: a histogram and its counter are updated
    under one lock, but a scrape renders families one at a time, so a
    mid-run snapshot can legally catch them apart.  Returns failure
    strings (empty = all invariants hold).
    """
    problems = []
    # engine: the latency histogram observes every completed request
    completed = metrics.get(
        "repro_engine_requests_completed_total", {}).get((), 0.0)
    lat_count = metrics.get(
        "repro_engine_request_latency_ms_count", {}).get((), 0.0)
    if completed != lat_count:
        problems.append(
            f"latency histogram count {lat_count} != "
            f"requests_completed_total {completed}")
    # http: per route, the latency histogram count == sum over statuses
    http_hist = metrics.get("repro_http_request_ms_count", {})
    http_total = metrics.get("repro_http_requests_total", {})
    by_route = {}
    for key, v in http_total.items():
        route = dict(key).get("route")
        by_route[route] = by_route.get(route, 0.0) + v
    for key, v in http_hist.items():
        route = dict(key).get("route")
        # the scrape currently being rendered hasn't counted itself yet
        if route == "/metrics":
            continue
        if by_route.get(route, 0.0) != v:
            problems.append(
                f"http histogram count {v} != status-counter sum "
                f"{by_route.get(route, 0.0)} for route {route}")
    return problems


def boot_server(args):
    """In-process server: engine + driver + HTTP listener on a free port."""
    from repro.engine import EngineConfig, EngineDriver, RetrievalEngine
    from repro.serve import TenantQuotas, serve_in_thread

    config = EngineConfig.from_flags(
        args, d_emb=args.dim,
        capacity=max(1024, args.tenants * args.docs * 2))
    # the isolation check tracks doc ids across the run; compaction remaps
    # them mid-flight, which is covered by the in-process hypothesis suite —
    # here we keep ids stable so leakage is exactly set membership
    config = dataclasses.replace(config, compact_dead_frac=None)
    engine = RetrievalEngine(config=config)
    driver = EngineDriver(engine, max_wait_ms=args.max_wait_ms,
                          max_queue=args.max_queue).start()
    quotas = TenantQuotas(
        max_inflight=args.max_inflight if args.max_inflight > 0 else None)
    handle = serve_in_thread(engine, driver, quotas=quotas)
    return handle, driver


def run_tenant_searches(url, tenant, queries, n_clients, k, results, qps):
    """Open-loop search threads for one tenant; appends per-request records
    ``(status, latency_s, ids, filtered_shard, spans)`` to ``results``."""
    shards = np.array_split(np.arange(len(queries)), n_clients)
    period = n_clients / qps if qps > 0 else 0.0
    lock = threading.Lock()
    rng = np.random.default_rng(abs(hash(tenant)) % (2 ** 31))
    filter_plan = rng.integers(-1, N_SHARDS, len(queries))  # -1 = unfiltered

    def client(shard):
        t_next = time.perf_counter()
        for i in shard:
            if period:
                now = time.perf_counter()
                if now < t_next:
                    time.sleep(t_next - now)
                t_next += period
            body = {"query": queries[i].tolist(), "tenant": tenant, "k": k}
            shard_tag = int(filter_plan[i])
            if shard_tag >= 0:
                body["filter"] = {"shard": {"$eq": shard_tag}}
            t0 = time.perf_counter()
            status, payload = http_json(url, "/v1/search", body)
            dt = time.perf_counter() - t0
            ids = payload.get("ids", []) if status == 200 else []
            spans = payload.get("spans") if status == 200 else None
            with lock:
                results.append((status, dt, ids, shard_tag, spans))

    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in shards if len(s)]
    for t in threads:
        t.start()
    return threads


def run_churn(url, tenant, dim, universe, universe_lock, stop, rng,
              statuses):
    """Add/delete loop for one tenant, racing the search traffic."""
    my_ids = []
    while not stop.is_set():
        vecs = rng.standard_normal((2, dim)).astype(np.float32)
        status, payload = http_json(url, "/v1/docs", {
            "vectors": vecs.tolist(), "tenant": tenant,
            "metadata": [{"shard": int(rng.integers(N_SHARDS)),
                          "churn": True} for _ in range(2)]})
        statuses.append(status)
        if status == 200:
            with universe_lock:
                universe[tenant].update(payload["ids"])
            my_ids.extend(payload["ids"])
        if len(my_ids) >= 4:
            victims, my_ids = my_ids[:2], my_ids[2:]
            status, _ = http_json(url, "/v1/docs/delete", {
                "ids": victims, "tenant": tenant})
            statuses.append(status)
        time.sleep(0.002)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", type=int, default=2,
                    help="isolated namespaces driven concurrently (>= 2)")
    ap.add_argument("--docs", type=int, default=1000,
                    help="seeded docs per tenant")
    ap.add_argument("--requests", type=int, default=128,
                    help="searches per tenant")
    ap.add_argument("--clients", type=int, default=4,
                    help="open-loop search threads per tenant")
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--qps", type=float, default=0.0,
                    help="per-tenant open-loop rate (0 = full speed)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--url", type=str, default="",
                    help="target a running server instead of self-hosting")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--max-queue", type=int, default=4096)
    ap.add_argument("--max-inflight", type=int, default=64,
                    help="per-tenant in-flight quota (0 = unlimited)")
    ap.add_argument("--out", type=str, default=None,
                    help="output JSON (default results/BENCH_http.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast run for CI (overrides sizes)")
    from repro.engine import EngineConfig
    EngineConfig.add_flags(ap)
    args = ap.parse_args()

    if args.smoke:
        args.tenants, args.docs, args.requests = 2, 200, 48
        args.clients, args.dim = 4, 64
        args.d_start, args.k0, args.final_k = 16, 16, 4
        args.buckets = "1,2,4,8"
    if args.tenants < 2:
        raise SystemExit("--tenants must be >= 2 (isolation is the point)")

    handle = driver = None
    if args.url:
        url = args.url
    else:
        handle, driver = boot_server(args)
        url = handle.url
    tenants = [f"tenant-{i}" for i in range(args.tenants)]
    rng = np.random.default_rng(args.seed)
    failures = []

    try:
        status, health = http_json(url, "/healthz")
        if status != 200:
            raise SystemExit(f"server unhealthy: {status} {health}")
        print(f"# http_load url={url} tenants={args.tenants} "
              f"docs/tenant={args.docs} requests/tenant={args.requests} "
              f"clients/tenant={args.clients} smoke={args.smoke}")

        # --- seed: metadata-tagged docs per tenant -------------------------
        universe = {t: set() for t in tenants}
        universe_lock = threading.Lock()
        for t in tenants:
            vecs = rng.standard_normal((args.docs, args.dim)).astype(
                np.float32)
            meta = [{"shard": j % N_SHARDS} for j in range(args.docs)]
            status, payload = http_json(url, "/v1/docs", {
                "vectors": vecs.tolist(), "tenant": t, "metadata": meta})
            if status != 200:
                raise SystemExit(f"seed failed for {t}: {status} {payload}")
            universe[t].update(payload["ids"])

        # --- measurement: searches + churn, all tenants at once ------------
        per_tenant_results = {t: [] for t in tenants}
        churn_statuses = {t: [] for t in tenants}
        stop_churn = threading.Event()
        churn_threads = [
            threading.Thread(
                target=run_churn,
                args=(url, t, args.dim, universe, universe_lock, stop_churn,
                      np.random.default_rng(args.seed + 100 + i),
                      churn_statuses[t]),
                daemon=True)
            for i, t in enumerate(tenants)]
        for ct in churn_threads:
            ct.start()
        search_threads = []
        t0 = time.perf_counter()
        for t in tenants:
            queries = rng.standard_normal(
                (args.requests, args.dim)).astype(np.float32)
            search_threads += run_tenant_searches(
                url, t, queries, max(1, min(args.clients, args.requests)),
                args.final_k, per_tenant_results[t], args.qps)
        # mid-run observability check: the exposition must parse while the
        # search/churn traffic is in full flight (parse_prometheus raises
        # on a malformed line, which lands in failures below)
        midrun_metric_names = 0
        try:
            time.sleep(0.05)
            midrun, _ = scrape_metrics(url)
            midrun_metric_names = len(midrun)
        except Exception as e:
            failures.append(f"mid-run /metrics scrape failed: {e}")
        for st in search_threads:
            st.join()
        wall = time.perf_counter() - t0
        stop_churn.set()
        for ct in churn_threads:
            ct.join(timeout=30)

        # --- verdicts ------------------------------------------------------
        records = []
        total_ok = total_429 = total_bad = total_leaks = 0
        print("tenant,requests,ok,throttled,bad,qps,p50_ms,p95_ms,leaks")
        for t in tenants:
            rows = per_tenant_results[t]
            lat_ms = [dt * 1e3 for s, dt, _, _, _ in rows if s == 200]
            # shared bucket ladder: same percentile math as /metrics
            pct = summarize_latency(lat_ms)
            n_ok = sum(1 for s, _, _, _, _ in rows if 200 <= s < 300)
            n_429 = sum(1 for s, _, _, _, _ in rows if s == 429)
            bad = [s for s, _, _, _, _ in rows
                   if not (200 <= s < 300 or s == 429)]
            bad += [s for s in churn_statuses[t]
                    if not (200 <= s < 300 or s == 429)]
            # isolation: every id ever returned to t was added under t
            # (universes only grow, so checking after the join is race-free)
            leaks = sum(1 for s, _, ids, _, _ in rows if s == 200
                        for i in ids if i not in universe[t])
            # trace spans: every served response must decompose its
            # latency, with the queue-wait span always present
            no_span = sum(
                1 for s, _, _, _, spans in rows if s == 200
                and (spans is None or spans.get("queue_ms") is None))
            rec = {
                "tenant": t,
                "requests": len(rows),
                "n_ok": n_ok,
                "n_throttled": n_429,
                "n_bad_status": len(bad),
                "qps": len(rows) / wall,
                "latency_ms_p50": pct["p50"],
                "latency_ms_p95": pct["p95"],
                "isolation_violations": leaks,
                "churn_ops": len(churn_statuses[t]),
                "n_missing_spans": no_span,
            }
            records.append(rec)
            total_ok += n_ok
            total_429 += n_429
            total_bad += len(bad)
            total_leaks += leaks
            if bad:
                failures.append(
                    f"{t}: {len(bad)} non-2xx/429 responses "
                    f"(e.g. {bad[:3]})")
            if leaks:
                failures.append(f"{t}: {leaks} cross-tenant ids returned")
            if no_span:
                failures.append(
                    f"{t}: {no_span} responses missing queue-wait spans")
            print(f"{t},{rec['requests']},{n_ok},{n_429},{len(bad)},"
                  f"{rec['qps']:.1f},{rec['latency_ms_p50']:.2f},"
                  f"{rec['latency_ms_p95']:.2f},{leaks}")

        # quiescent scrape: histogram/_count-vs-counter invariants only
        # hold once traffic stops (families render one at a time)
        n_metric_names = 0
        try:
            final_metrics, _ = scrape_metrics(url)
            n_metric_names = len(final_metrics)
            failures.extend(check_histogram_counter_pairs(final_metrics))
        except Exception as e:
            failures.append(f"final /metrics scrape failed: {e}")

        out_path = args.out or os.path.join(
            os.path.dirname(__file__), "..", "results", "BENCH_http.json")
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump({
                "benchmark": "http_load",
                "smoke": args.smoke,
                "tenants": args.tenants,
                "docs_per_tenant": args.docs,
                "requests_per_tenant": args.requests,
                "clients_per_tenant": args.clients,
                "dim": args.dim,
                "wall_s": wall,
                "qps_total": total_ok / wall if wall else 0.0,
                "n_ok": total_ok,
                "n_throttled": total_429,
                "n_bad_status": total_bad,
                "isolation_violations": total_leaks,
                "metric_families_midrun": midrun_metric_names,
                "metric_families_final": n_metric_names,
                "records": records,
            }, f, indent=2)
        print(f"# wrote {os.path.normpath(out_path)}")
    finally:
        if handle is not None:
            handle.stop()
        if driver is not None:
            driver.stop()

    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        raise SystemExit(1)
    print(f"# OK: {total_ok} served, {total_429} throttled, "
          f"0 bad statuses, 0 isolation violations")


if __name__ == "__main__":
    main()
