"""Observability-overhead benchmark: instrumented vs ``obs.enabled=False``.

PR 7's contract is that the telemetry spine (registry counters/histograms
mirrored under ``engine.lock``, per-request trace contexts, the trace
ring) costs <= ``--tolerance`` (default 5%) of queued-path QPS, and that
``obs.enabled=False`` restores the uninstrumented fast path (no-op
instruments, no TraceContext allocation).  This benchmark measures both
modes on the same corpus/schedule and fails the run when the gap exceeds
the tolerance.

Methodology: the two engines are driven in alternating repetitions (so a
machine-load drift hits both modes, not one), with the within-pair order
flipped every repetition (so a systematic order effect — cache warming,
CPU frequency ramp — cancels instead of biasing one mode).  The reported
overhead compares the *median QPS of each mode* across its repetitions:
medians reject the one slow outlier rep (GC pause, CI neighbour), and
because the modes' samples interleave in time, slow drift moves both
medians together instead of biasing the difference.  Per-pair estimates
and per-mode best-of QPS are recorded alongside for reference.

    PYTHONPATH=src python -m benchmarks.obs_overhead --smoke
    PYTHONPATH=src python -m benchmarks.obs_overhead \
        --docs 20000 --dim 256 --requests 512 --reps 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def build_engine(db, args, *, enabled):
    from repro.engine import RetrievalEngine
    from repro.engine.config import ObsConfig

    eng = RetrievalEngine(
        db.shape[1], d_start=args.d_start, k0=args.k0,
        buckets=tuple(int(x) for x in args.buckets.split(",")),
        capacity=db.shape[0],
        obs=ObsConfig(enabled=enabled),
    )
    eng.add_docs(db)
    eng.warmup()
    return eng


def run_once(eng, queries) -> float:
    """One queued-path repetition; returns QPS."""
    t0 = time.perf_counter()
    rids = [eng.submit(q) for q in queries]
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    for rid in rids:
        assert eng.poll(rid) is not None
    return len(queries) / wall


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--docs", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--reps", type=int, default=3,
                    help="alternating repetitions per mode (best-of wins)")
    ap.add_argument("--d-start", type=int, default=32)
    ap.add_argument("--k0", type=int, default=32)
    ap.add_argument("--buckets", type=str, default="1,2,4,8,16,32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="max allowed fractional QPS loss when instrumented")
    ap.add_argument("--out", type=str, default=None,
                    help="output JSON (default results/BENCH_obs.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast run for CI (overrides sizes)")
    args = ap.parse_args()

    if args.smoke:
        # compute-representative but CI-small: per-request dispatch work
        # must dominate Python per-request cost, or the percentage gate
        # measures the corpus size instead of the instrumentation
        args.docs, args.dim, args.requests = 16384, 256, 512
        args.d_start, args.k0 = 32, 32
        args.buckets = "1,2,4,8"
        args.reps = max(args.reps, 7)

    from repro.rag import make_corpus

    corpus = make_corpus(n_docs=args.docs, dim=args.dim,
                         n_queries=args.requests, seed=args.seed)

    print(f"# obs_overhead docs={args.docs} dim={args.dim} "
          f"requests={args.requests} reps={args.reps} smoke={args.smoke}")
    eng_on = build_engine(corpus.db, args, enabled=True)
    eng_off = build_engine(corpus.db, args, enabled=False)

    qps_on, qps_off, pair_overheads = [], [], []
    for rep in range(max(1, args.reps)):
        if rep % 2 == 0:
            a = run_once(eng_on, corpus.queries)
            b = run_once(eng_off, corpus.queries)
        else:
            b = run_once(eng_off, corpus.queries)
            a = run_once(eng_on, corpus.queries)
        qps_on.append(a)
        qps_off.append(b)
        pair_overheads.append((b - a) / b if b > 0 else 0.0)

    def median(xs):
        ranked = sorted(xs)
        n = len(ranked)
        return (ranked[n // 2] if n % 2
                else (ranked[n // 2 - 1] + ranked[n // 2]) / 2)

    best_on, best_off = max(qps_on), max(qps_off)
    med_on, med_off = median(qps_on), median(qps_off)
    overhead = (med_off - med_on) / med_off if med_off > 0 else 0.0
    # sanity: the instrumented engine really recorded, the bare one didn't
    scrape = eng_on.metrics.render_prometheus()
    instrumented_ok = (
        "repro_engine_requests_completed_total" in scrape
        and eng_on.metrics.enabled and not eng_off.metrics.enabled)

    print("mode,qps_median,qps_best,qps_all")
    print(f"obs_on,{med_on:.1f},{best_on:.1f},"
          f"\"{','.join(f'{q:.1f}' for q in qps_on)}\"")
    print(f"obs_off,{med_off:.1f},{best_off:.1f},"
          f"\"{','.join(f'{q:.1f}' for q in qps_off)}\"")
    print(f"# overhead={overhead * 100:.2f}% (mode medians over "
          f"{len(pair_overheads)} alternating reps; tolerance "
          f"{args.tolerance * 100:.0f}%)")

    out_path = args.out or os.path.join(
        os.path.dirname(__file__), "..", "results", "BENCH_obs.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({
            "benchmark": "obs_overhead",
            "smoke": args.smoke,
            "docs": args.docs,
            "dim": args.dim,
            "requests": args.requests,
            "reps": args.reps,
            "qps_instrumented": med_on,
            "qps_disabled": med_off,
            "qps_instrumented_best": best_on,
            "qps_disabled_best": best_off,
            "qps_instrumented_all": qps_on,
            "qps_disabled_all": qps_off,
            "overhead_pairs": pair_overheads,
            "overhead_frac": overhead,
            "tolerance": args.tolerance,
            "instrumented_registry_ok": instrumented_ok,
        }, f, indent=2)
    print(f"# wrote {os.path.normpath(out_path)}")

    if not instrumented_ok:
        raise SystemExit("FAIL: instrumented registry did not record "
                         "(or the disabled one did)")
    if overhead > args.tolerance:
        raise SystemExit(
            f"FAIL: instrumentation overhead {overhead * 100:.2f}% "
            f"(mode medians) exceeds {args.tolerance * 100:.0f}% "
            f"tolerance (on={med_on:.1f} qps, off={med_off:.1f} qps)")
    print("# OK: instrumentation overhead within tolerance")


if __name__ == "__main__":
    main()
