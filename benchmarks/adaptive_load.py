"""Adaptive-serving benchmark: recall vs load with and without the policy.

Four phases over one clustered corpus, all recorded to
``results/BENCH_adaptive.json``:

1. **bit-for-bit** — an engine with the adaptive sections constructed but
   idle (level 0) must reproduce the static engine's top-k ids exactly
   (acceptance (c): enabling the subsystem cannot perturb results).
2. **degradation curve** — recall@10 measured per pressure level by
   dispatching the full eval set through ``overrides_for_level``:
   the recall-vs-degradation trade the policy moves along.
3. **overload** — the same open-loop burst (clients submitting far faster
   than the service rate) against a static driver and an adaptive driver.
   The policy must shed knobs (escalations > 0) and cut client p95
   while keeping delivered recall@10 near the idle value
   (acceptance (a): p95 <= 0.7x static at recall >= 0.95x idle).
4. **cache replay** — a hot query set replayed through the driver's
   query cache must hit >= 90%; one store mutation must drop the next
   replay's scrape-delta hit rate to exactly 0 (acceptance (b)).

Exit status is non-zero if any enforced check fails.  ``--smoke``
(CI) enforces the deterministic checks — bit-for-bit, zero-load recall
equality, escalation-under-overload, cache replay — and skips only the
wall-clock p95 ratio, which needs the full-size run to be meaningful.

    PYTHONPATH=src python -m benchmarks.adaptive_load --smoke
    PYTHONPATH=src python -m benchmarks.adaptive_load
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

K = 10          # recall@10 throughout


def build_engine(db, *, adaptive, cache, args):
    from repro.engine import AdaptiveConfig, CacheConfig, RetrievalEngine

    acfg = AdaptiveConfig(
        enabled=adaptive, levels=2,
        depth_high=args.depth_high, wait_high_ms=None,
        hysteresis_s=30.0,                    # never recover mid-burst
        n_probe_scale=args.n_probe_scale, oversample_scale=0.5,
        d_start_shift=1, min_d_start=max(16, args.d_start // 4))
    eng = RetrievalEngine(
        db.shape[1], d_start=args.d_start, k0=args.k0, final_k=K,
        buckets=(1, 2, 4, 8), capacity=len(db), block_n=len(db),
        backend="ivf",
        backend_opts=dict(n_lists=args.n_lists, n_probe=args.n_probe),
        adaptive=acfg if adaptive else None,
        cache=CacheConfig(enabled=True, capacity=args.cache_capacity)
        if cache else None,
    )
    eng.add_docs(db)
    eng.warmup()                              # all buckets x all levels
    return eng


def exact_topk(db, queries, k=K):
    """Ground-truth L2 top-k ids, blockwise numpy."""
    out = np.empty((len(queries), k), np.int64)
    for i, q in enumerate(queries):
        d = ((db - q[None, :]) ** 2).sum(axis=1)
        idx = np.argpartition(d, k)[:k]
        out[i] = idx[np.argsort(d[idx])]
    return out


def recall_at_k(ids, truth):
    """Mean |retrieved ∩ exact| / k."""
    hits = sum(len(set(map(int, a)) & set(map(int, b)))
               for a, b in zip(ids, truth))
    return hits / (len(truth) * truth.shape[1])


def level_recall_curve(eng, queries, truth):
    """Phase 2: recall@10 dispatched at each pressure level."""
    from repro.engine import SearchRequest

    curve = []
    for lvl in range(0, eng.config.adaptive.levels + 1):
        ov = eng.overrides_for_level(lvl)
        ids = []
        for q in queries:
            reqs = [eng.check_request(SearchRequest(q))]
            (res,) = eng.execute_batch(reqs, overrides=ov)
            ids.append(res.doc_ids)
        curve.append({"level": lvl, "recall_at_10":
                      recall_at_k(np.asarray(ids), truth)})
    return curve


def overload_run(db, queries, truth, *, adaptive, args):
    """Phase 3: open-loop burst; returns client-side p95 + recall."""
    from repro.engine import EngineDriver
    from repro.launch.serve import run_clients

    eng = build_engine(db, adaptive=adaptive, cache=False, args=args)
    driver = EngineDriver(eng, max_wait_ms=2.0,
                          max_queue=max(4096, len(queries))).start()
    try:
        results, wall = run_clients(driver, queries, args.clients,
                                    qps=0.0, timeout=600.0)
    finally:
        summary = (driver.adaptive.summary() if driver.adaptive is not None
                   else {"enabled": False})
        driver.stop()
    lat = np.array([r.stats.latency_ms for r in results])
    ids = np.stack([r.doc_ids for r in results])
    levels = np.array([r.degraded_level for r in results])
    return {
        "adaptive": adaptive,
        "requests": len(queries),
        "clients": args.clients,
        "qps": len(queries) / wall,
        "latency_ms_p50": float(np.percentile(lat, 50)),
        "latency_ms_p95": float(np.percentile(lat, 95)),
        "recall_at_10": recall_at_k(ids, truth),
        "degraded_requests": int((levels > 0).sum()),
        "policy": summary,
    }


def cache_replay(db, hot, *, args):
    """Phase 4: hot-set replay hit rate, then a mutation -> zero hits."""
    from repro.engine import EngineDriver
    from repro.obs import parse_prometheus

    eng = build_engine(db, adaptive=False, cache=True, args=args)
    driver = EngineDriver(eng, max_wait_ms=0.0).start()

    def scrape():
        m = parse_prometheus(eng.metrics.render_prometheus())
        hits = (m.get("repro_qcache_hits_total", {}).get(
                    (("kind", "exact"),), 0.0)
                + m.get("repro_qcache_hits_total", {}).get(
                    (("kind", "near"),), 0.0))
        misses = m.get("repro_qcache_misses_total", {}).get((), 0.0)
        return hits, misses

    try:
        for _ in range(args.replays):
            for q in hot:
                driver.retrieve(q, timeout=120)
        hits, misses = scrape()
        total = hits + misses
        hit_rate = hits / total if total else 0.0

        # one store mutation: the very next scrape window must be all
        # misses — the stamp flush makes a stale hit structurally
        # impossible
        eng.add_docs(np.random.default_rng(5).normal(
            size=(1, db.shape[1])).astype(np.float32))
        h0, m0 = scrape()
        for q in hot:
            driver.retrieve(q, timeout=120)
        h1, m1 = scrape()
        post_rate = ((h1 - h0) / ((h1 - h0) + (m1 - m0))
                     if (h1 - h0) + (m1 - m0) else 0.0)
        inval = driver.cache.summary()["invalidations"]
    finally:
        driver.stop()
    return {
        "hot_queries": len(hot),
        "replays": args.replays,
        "hit_rate": hit_rate,
        "post_mutation_hit_rate": post_rate,
        "invalidations": inval,
    }


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--docs", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--overload-requests", type=int, default=512)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--d-start", type=int, default=64)
    ap.add_argument("--k0", type=int, default=256)
    ap.add_argument("--n-lists", type=int, default=32)
    ap.add_argument("--n-probe", type=int, default=16)
    ap.add_argument("--n-probe-scale", type=float, default=0.7)
    ap.add_argument("--alpha", type=float, default=0.6,
                    help="corpus spectrum decay: steeper = more signal in "
                         "the truncated dims the degraded schedules keep")
    ap.add_argument("--depth-high", type=int, default=8)
    ap.add_argument("--cache-capacity", type=int, default=256)
    ap.add_argument("--replays", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI run; skips the wall-clock p95 check")
    args = ap.parse_args()

    if args.smoke:
        args.docs, args.dim, args.queries = 3000, 64, 48
        args.overload_requests, args.clients = 192, 8
        args.d_start, args.k0 = 32, 128
        args.n_lists, args.n_probe = 16, 8
        args.n_probe_scale = 0.85
        args.cache_capacity, args.replays = 64, 16

    from repro.rag import make_corpus

    corpus = make_corpus(n_docs=args.docs, dim=args.dim,
                         n_queries=max(args.queries,
                                       args.overload_requests),
                         seed=args.seed, alpha=args.alpha)
    db = np.asarray(corpus.db, np.float32)
    all_q = np.asarray(corpus.queries, np.float32)
    eval_q = all_q[:args.queries]
    load_q = all_q[:args.overload_requests]
    truth_eval = exact_topk(db, eval_q)
    truth_load = exact_topk(db, load_q)

    print(f"# adaptive_load docs={args.docs} dim={args.dim} "
          f"smoke={args.smoke}")

    # -- phase 1: bit-for-bit with the subsystem idle -------------------
    static_eng = build_engine(db, adaptive=False, cache=False, args=args)
    adaptive_eng = build_engine(db, adaptive=True, cache=False, args=args)
    _, ids_static = static_eng.search(eval_q)
    _, ids_idle = adaptive_eng.search(eval_q)
    bit_for_bit = bool(np.array_equal(ids_static, ids_idle))
    recall_static = recall_at_k(ids_static, truth_eval)
    recall_idle = recall_at_k(ids_idle, truth_eval)
    print(f"bit_for_bit={bit_for_bit} recall_idle={recall_idle:.4f}")

    # -- phase 2: recall per degradation level --------------------------
    curve = level_recall_curve(adaptive_eng, eval_q, truth_eval)
    for c in curve:
        print(f"level={c['level']} recall@10={c['recall_at_10']:.4f}")
    del static_eng, adaptive_eng

    # -- phase 3: overload with/without the policy ----------------------
    static_run = overload_run(db, load_q, truth_load,
                              adaptive=False, args=args)
    adaptive_run = overload_run(db, load_q, truth_load,
                                adaptive=True, args=args)
    p95_ratio = (adaptive_run["latency_ms_p95"]
                 / max(static_run["latency_ms_p95"], 1e-9))
    recall_ratio = adaptive_run["recall_at_10"] / max(recall_idle, 1e-9)
    print(f"overload: static p95={static_run['latency_ms_p95']:.1f}ms "
          f"adaptive p95={adaptive_run['latency_ms_p95']:.1f}ms "
          f"ratio={p95_ratio:.3f} recall_ratio={recall_ratio:.4f} "
          f"escalations={adaptive_run['policy'].get('n_escalations')}")

    # -- phase 4: cache replay + mutation -------------------------------
    hot = all_q[:20]
    cache = cache_replay(db, hot, args=args)
    print(f"cache: hit_rate={cache['hit_rate']:.4f} "
          f"post_mutation={cache['post_mutation_hit_rate']:.4f}")

    checks = {
        # (c): enabling the subsystem at level 0 is invisible
        "bit_for_bit": bit_for_bit,
        # smoke condition: zero-load recall identical to the baseline
        "idle_recall_matches_static": recall_idle == recall_static,
        # smoke condition: the policy actually shed knobs under overload
        "policy_escalated": (
            adaptive_run["policy"].get("n_escalations", 0) > 0
            and adaptive_run["degraded_requests"] > 0),
        # (b): hot replay >= 90% hit, mutation zeroes the next window
        "cache_hit_rate_ge_90": cache["hit_rate"] >= 0.90,
        "mutation_drops_hit_rate_to_0":
            cache["post_mutation_hit_rate"] == 0.0,
        # (a): the wall-clock trade, meaningful only at full size
        "overload_p95_le_0.7x_static": p95_ratio <= 0.70,
        "overload_recall_ge_0.95x_idle": recall_ratio >= 0.95,
    }
    enforced = [k for k in checks
                if not (args.smoke and k == "overload_p95_le_0.7x_static")]

    record = {
        "bench": "adaptive_load",
        "smoke": args.smoke,
        "config": {
            "docs": args.docs, "dim": args.dim,
            "d_start": args.d_start, "k0": args.k0, "k": K,
            "n_lists": args.n_lists, "n_probe": args.n_probe,
            "depth_high": args.depth_high,
            "overload_requests": args.overload_requests,
            "clients": args.clients,
        },
        "bit_for_bit": bit_for_bit,
        "recall_idle": recall_idle,
        "recall_static": recall_static,
        "level_recall": curve,
        "overload": {"static": static_run, "adaptive": adaptive_run,
                     "p95_ratio": p95_ratio, "recall_ratio": recall_ratio},
        "cache": cache,
        "checks": checks,
    }

    out = args.out or os.path.join(os.path.dirname(__file__), "..",
                                   "results", "BENCH_adaptive.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {os.path.normpath(out)}")

    failed = [k for k in enforced if not checks[k]]
    if failed:
        print(f"FAILED checks: {failed}", file=sys.stderr)
        sys.exit(1)
    print("all checks passed")


if __name__ == "__main__":
    main()
