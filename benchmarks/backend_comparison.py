"""Index-backend comparison: QPS / latency / recall per backend and corpus size.

For each corpus size, replays a single-query request stream through
``RetrievalEngine`` once per backend (``flat`` / ``ivf`` / ``quantized``)
and reports build time, steady-state QPS, p50/p95 request latency, and
recall@k against exact full-dimensional search.  The corpus is the
*clustered* synthetic workload (`repro.rag.make_clustered_corpus`) — the
topical structure real document embeddings carry and the prior an IVF
coarse quantizer exploits; `benchmarks/engine_throughput.py` covers the
unclustered truncation-profile corpus.

Writes ``results/BENCH_backends.json`` for CI/regression tracking.

``--ivf-kernel`` switches to the fused-kernel comparison: the ``ivf``
backend runs once per stage-0 path (XLA gather+rescore, fused Pallas
kernel, fused int8 member slabs) and each record carries the *modeled*
stage-0 HBM bytes/query from `repro.kernels.ivf_scan.stage0_bytes_model`
alongside measured QPS and recall — the acceptance check is that the fused
paths model strictly fewer bytes.  On CPU the kernel runs in interpret
mode, so its *measured* QPS understates real-TPU throughput (the modeled
bytes are the hardware-relevant number); writes
``results/BENCH_ivf_kernel.json``.

``--pq`` compares the product-quantized stage-0 paths against their int8
counterparts: the ``quantized`` backend per codec (int8 XLA, PQ ADC XLA,
PQ fused LUT kernel) plus the fused IVF int8/PQ pairs, each record
carrying modeled stage-0 bytes/query.  Acceptance: every PQ path must
model strictly fewer stage-0 bytes than its int8 counterpart, and (full
runs) the PQ backend must reach recall@k >= 0.95 vs exact at < 0.5x the
int8 bytes at the largest corpus.  Fused (interpret-mode) runs are
skipped on CPU past 4096 docs — the interpreter is minutes/query there
and the modeled bytes are the hardware-relevant number; parity is pinned
by `tests/test_kernels.py` instead.  Writes ``results/BENCH_pq.json``.

    PYTHONPATH=src python -m benchmarks.backend_comparison [--smoke]
    PYTHONPATH=src python -m benchmarks.backend_comparison \
        --sizes 8192,65536 --dim 256 --requests 256
    PYTHONPATH=src python -m benchmarks.backend_comparison --smoke --ivf-kernel
    PYTHONPATH=src python -m benchmarks.backend_comparison --smoke --pq
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


BACKEND_OPTS = {
    "flat": None,
    "ivf": None,        # backend defaults: n_lists ~ N/64, n_probe=12, bf=2.0
    "quantized": None,
}


def _stage0_bytes(eng):
    """Modeled stage-0 HBM bytes/query for the engine's live index state.

    IVF states use the probe-scan model (`stage0_bytes_model`), quantized
    code-block states the flat-scan model (`flat_stage0_bytes_model`);
    the record carries the byte count of the path the engine actually
    serves (XLA vs fused kernel).
    """
    from repro.kernels.ivf_scan import stage0_bytes_model
    from repro.kernels.pq_scan import flat_stage0_bytes_model

    state = eng.index_state
    if state is None or state.data.get("flat"):
        return None
    d0 = eng.sched.stages[0].dim
    k0 = eng.sched.stages[0].k

    if "codec" in state.data:                  # quantized code-block scan
        idx = state.data["idx"]
        if state.data["codec"] == "pq":
            m, c = idx["codebooks"].shape[0], idx["codebooks"].shape[1]
            row_bytes, lut_bytes = m, m * c * 4
        else:
            row_bytes, lut_bytes = d0, 0.0
        model = flat_stage0_bytes_model(
            n=state.data["n_coded"], k=k0,
            row_bytes=row_bytes, lut_bytes=lut_bytes)
        fused = eng.backend._kernel_enabled()
        return {
            "stage0_path": "fused" if fused else "xla",
            "stage0_hbm_bytes_per_query": (
                model["fused_bytes"] if fused else model["xla_bytes"]),
            "stage0_bytes_model": model,
        }

    if "n_lists" not in state.data:
        return None
    pack = state.data.get("pack")
    max_len = pack["max_len"] if pack else state.data["max_len"]
    row_bytes = lut_bytes = None
    norms = True
    if pack and pack["dtype"] == "pq":
        m, c = pack["codebooks"].shape[0], pack["codebooks"].shape[1]
        row_bytes, lut_bytes, norms = m, m * c * 4, False
    model = stage0_bytes_model(
        n_lists=state.data["n_lists"],
        max_len=max_len,
        n_probe=min(eng.backend.n_probe, state.data["n_lists"]),
        d0=d0,
        k=k0,
        member_bytes=1 if (pack and pack["dtype"] == "int8") else 4,
        row_bytes=row_bytes,
        lut_bytes=lut_bytes or 0.0,
        norms=norms,
    )
    fused = pack is not None
    return {
        "stage0_path": "fused" if fused else "xla",
        "stage0_hbm_bytes_per_query": (
            model["fused_bytes"] if fused else model["xla_bytes"]),
        "stage0_bytes_model": model,
    }


def run_backend(corpus, backend, *, d_start, k0, k, buckets, exact_ids,
                backend_opts=None, label=None):
    import jax.numpy as jnp

    from repro.core import overlap_at_k, recall_at_k
    from repro.engine import RetrievalEngine

    n_docs = corpus.db.shape[0]
    eng = RetrievalEngine(
        corpus.db.shape[1], d_start=d_start, k0=k0, final_k=k,
        buckets=buckets, capacity=n_docs, backend=backend,
        backend_opts=backend_opts,
        # the replay drains the whole stream before polling: no result may
        # be evicted, however large --requests is
        max_unpolled=max(65536, len(corpus.queries)),
    )
    eng.add_docs(corpus.db)
    t0 = time.perf_counter()
    eng.maybe_rebuild(force=True)         # isolate the index build cost
    build_s = time.perf_counter() - t0
    eng.warmup()

    t0 = time.perf_counter()
    rids = [eng.submit(q) for q in corpus.queries]
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    results = [eng.poll(r) for r in rids]
    ids = np.stack([r.doc_ids for r in results])

    s = eng.stats.summary()
    state = eng.index_state
    bytes_info = _stage0_bytes(eng)
    return {
        "backend": backend,
        "label": label or backend,
        **(bytes_info or {}),
        "docs": n_docs,
        "build_s": build_s,
        "qps": len(rids) / wall,
        "latency_ms_p50": s["latency_ms_p50"],
        "latency_ms_p95": s["latency_ms_p95"],
        "recall_at_k_vs_exact": float(
            overlap_at_k(jnp.asarray(ids), jnp.asarray(exact_ids), k)),
        "recall_at_k_gt": float(
            recall_at_k(jnp.asarray(ids),
                        jnp.asarray(corpus.ground_truth), k)),
        "state_shape_key": list(map(str, state.shape_key)) if state else None,
    }


def _check_pq(records, by, largest, args) -> None:
    """--pq acceptance: every PQ path models strictly fewer stage-0 bytes
    than its int8 counterpart; full (non-smoke) runs additionally demand
    recall@k >= 0.95 vs exact at < 0.5x the int8 bytes at the largest
    corpus (the tentpole's acceptance numbers)."""
    pairs = [("quantized-pq", "quantized-int8"),
             ("quantized-pq-fused", "quantized-int8"),
             ("ivf-pq-fused", "ivf-int8-fused")]
    checked = 0
    for pq_label, int8_label in pairs:
        # compare at the largest size where BOTH paths ran (fused runs are
        # size-gated on CPU)
        common = [r["docs"] for r in records if r["label"] == pq_label
                  if any(o["label"] == int8_label and o["docs"] == r["docs"]
                         for o in records)]
        if not common:
            continue
        docs = max(common)
        pq = next(r for r in records
                  if r["label"] == pq_label and r["docs"] == docs)
        i8 = next(r for r in records
                  if r["label"] == int8_label and r["docs"] == docs)
        pq_b = pq.get("stage0_hbm_bytes_per_query")
        i8_b = i8.get("stage0_hbm_bytes_per_query")
        if pq_b is None or i8_b is None:
            raise SystemExit(
                f"{pq_label} @ {docs} docs has no stage-0 bytes model "
                f"(flat fallback served?); use sizes >= 64")
        ok = pq_b < i8_b
        print(f"# {pq_label} @ {docs} docs: modeled stage-0 "
              f"{pq_b/1e3:.1f} kB/q vs {int8_label} {i8_b/1e3:.1f} kB/q "
              f"({pq_b/i8_b:.3f}x) recall@{args.k}="
              f"{pq['recall_at_k_vs_exact']:.3f} {'OK' if ok else 'FAIL'}")
        if not ok:
            raise SystemExit(
                f"{pq_label} models >= {int8_label} stage-0 bytes "
                f"({pq_b} >= {i8_b})")
        checked += 1
    if not checked:
        raise SystemExit("--pq ran no comparable int8/PQ pairs")
    if not args.smoke:
        pq = by.get("quantized-pq")
        i8 = by.get("quantized-int8")
        if pq and i8:
            ratio = (pq["stage0_hbm_bytes_per_query"]
                     / i8["stage0_hbm_bytes_per_query"])
            recall = pq["recall_at_k_vs_exact"]
            print(f"# acceptance @ {largest} docs: recall@{args.k}="
                  f"{recall:.3f} (need >= 0.95), bytes ratio={ratio:.3f} "
                  f"(need < 0.5)")
            if recall < 0.95:
                raise SystemExit(
                    f"quantized-pq recall@{args.k}={recall:.3f} < 0.95 "
                    f"at {largest} docs")
            if ratio >= 0.5:
                raise SystemExit(
                    f"quantized-pq models {ratio:.3f}x of int8 stage-0 "
                    f"bytes at {largest} docs (need < 0.5)")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sizes", type=str, default="8192,24576,65536",
                    help="comma-separated corpus sizes")
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--d-start", type=int, default=64)
    ap.add_argument("--k0", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--buckets", type=str, default="32")
    ap.add_argument("--backends", type=str, default="flat,ivf,quantized")
    ap.add_argument("--ivf-kernel", action="store_true",
                    help="compare the ivf backend's stage-0 paths (XLA vs "
                         "fused Pallas kernel vs fused int8) instead of the "
                         "backend sweep; writes BENCH_ivf_kernel.json")
    ap.add_argument("--pq", action="store_true",
                    help="compare the product-quantized stage-0 paths "
                         "against their int8 counterparts (quantized "
                         "backend per codec + fused IVF int8/PQ); fails "
                         "unless every PQ path models strictly fewer "
                         "stage-0 bytes than int8; writes BENCH_pq.json")
    ap.add_argument("--out", type=str, default=None,
                    help="output JSON (default results/BENCH_backends.json; "
                         "BENCH_ivf_kernel.json with --ivf-kernel; "
                         "BENCH_pq.json with --pq)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast run for CI (overrides sizes)")
    args = ap.parse_args()

    if args.smoke:
        args.sizes, args.dim, args.requests = "512,1024", 64, 48
        args.d_start, args.k0, args.k = 8, 32, 5

    from repro.core import truncated_search
    from repro.rag import make_clustered_corpus
    import jax.numpy as jnp

    sizes = [int(x) for x in args.sizes.split(",")]
    buckets = tuple(int(x) for x in args.buckets.split(","))
    if args.ivf_kernel and args.pq:
        raise SystemExit("--ivf-kernel and --pq are mutually exclusive")

    import jax

    # interpret-mode (CPU) fused runs past this corpus size take
    # minutes/query; the modeled bytes are the hardware-relevant number
    # and kernel parity is pinned by the tier-1 tests
    fused_ok_docs = float("inf") if jax.default_backend() == "tpu" else 4096

    def runs_for(n_docs):
        if args.ivf_kernel:
            # one ivf run per stage-0 path; use_kernel=True is interpret
            # mode on CPU (parity-true, slow) and the real kernel on TPU
            return [
                ("ivf-xla", "ivf", {"use_kernel": False}),
                ("ivf-fused", "ivf", {"use_kernel": True}),
                ("ivf-fused-int8", "ivf",
                 {"use_kernel": True, "stage0_dtype": "int8"}),
            ]
        if args.pq:
            runs = [
                ("quantized-int8", "quantized", {"codec": "int8"}),
                ("quantized-pq", "quantized", {"codec": "pq"}),
            ]
            if n_docs <= fused_ok_docs:
                runs += [
                    ("quantized-pq-fused", "quantized",
                     {"codec": "pq", "use_kernel": True}),
                    ("ivf-int8-fused", "ivf",
                     {"use_kernel": True, "stage0_dtype": "int8"}),
                    ("ivf-pq-fused", "ivf",
                     {"use_kernel": True, "stage0_dtype": "pq"}),
                ]
            else:
                print(f"# skipping fused (interpret-mode) runs at {n_docs} "
                      f"docs on {jax.default_backend()}")
            return runs
        return [(b, b, BACKEND_OPTS.get(b)) for b in args.backends.split(",")]

    print(f"# backend_comparison dim={args.dim} requests={args.requests} "
          f"k={args.k} smoke={args.smoke} ivf_kernel={args.ivf_kernel} "
          f"pq={args.pq}")
    print("docs,label,build_s,qps,p50_ms,p95_ms,recall@k_vs_exact")
    records = []
    for n_docs in sizes:
        corpus = make_clustered_corpus(
            n_docs=n_docs, dim=args.dim, n_queries=args.requests,
            seed=args.seed)
        _, exact_ids = truncated_search(
            jnp.asarray(corpus.queries), jnp.asarray(corpus.db),
            dim=args.dim, k=args.k, block_n=min(n_docs, 65536))
        exact_ids = np.asarray(exact_ids)
        for label, backend, opts in runs_for(n_docs):
            rec = run_backend(
                corpus, backend, d_start=args.d_start, k0=args.k0, k=args.k,
                buckets=buckets, exact_ids=exact_ids,
                backend_opts=opts, label=label,
            )
            records.append(rec)
            print(f"{n_docs},{label},{rec['build_s']:.2f},"
                  f"{rec['qps']:.1f},{rec['latency_ms_p50']:.2f},"
                  f"{rec['latency_ms_p95']:.2f},"
                  f"{rec['recall_at_k_vs_exact']:.3f}")

    largest = sizes[-1]
    by = {r["label"]: r for r in records if r["docs"] == largest}
    if args.pq:
        _check_pq(records, by, largest, args)
    elif args.ivf_kernel:
        # acceptance: every fused path must model strictly fewer stage-0
        # HBM bytes than the XLA lowering (the fusion's whole point)
        if any(r.get("stage0_hbm_bytes_per_query") is None
               for r in by.values()):
            raise SystemExit(
                f"corpus of {largest} docs is below the ivf backend's "
                f"min_index_rows (flat fallback served, no stage-0 model); "
                f"use --sizes with at least 64 docs")
        xla = by["ivf-xla"]["stage0_hbm_bytes_per_query"]
        for label in ("ivf-fused", "ivf-fused-int8"):
            fused = by[label]["stage0_hbm_bytes_per_query"]
            ok = fused < xla
            print(f"# {label} @ {largest} docs: modeled stage-0 "
                  f"{fused/1e3:.1f} kB/q vs xla {xla/1e3:.1f} kB/q "
                  f"({fused/xla:.3f}x) recall@{args.k}="
                  f"{by[label]['recall_at_k_vs_exact']:.3f} "
                  f"{'OK' if ok else 'FAIL'}")
            if not ok:
                raise SystemExit(
                    f"{label} models >= XLA stage-0 bytes ({fused} >= {xla})")
    elif "ivf" in by and "flat" in by:
        speedup = by["ivf"]["qps"] / max(by["flat"]["qps"], 1e-9)
        print(f"# ivf vs flat @ {largest} docs: {speedup:.2f}x QPS, "
              f"ivf recall@{args.k}={by['ivf']['recall_at_k_vs_exact']:.3f}")

    default_name = ("BENCH_ivf_kernel.json" if args.ivf_kernel
                    else "BENCH_pq.json" if args.pq
                    else "BENCH_backends.json")
    out_path = args.out or os.path.join(
        os.path.dirname(__file__), "..", "results", default_name)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    payload = {
        "benchmark": ("backend_comparison/ivf_kernel" if args.ivf_kernel
                      else "backend_comparison/pq" if args.pq
                      else "backend_comparison"),
        "dim": args.dim,
        "requests": args.requests,
        "k": args.k,
        "d_start": args.d_start,
        "k0": args.k0,
        "sizes": sizes,
        "smoke": args.smoke,
        "records": records,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# wrote {os.path.normpath(out_path)}")


if __name__ == "__main__":
    main()
