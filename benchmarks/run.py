"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--docs N] [--dim D]
    PYTHONPATH=src python -m benchmarks.run --list-bench

Order: Table II (truncated, gte) -> Table III (progressive vs truncated,
gte) -> Table IV (truncated, openai) -> Table V (progressive, openai) ->
Fig 3/4 scatter -> kernel micro-validation -> roofline summary (if the
dry-run sweep has produced results/dryrun/*.json).

``run.py`` itself prints paper tables; the committed ``results/BENCH_*.json``
perf records are refreshed by the sibling modules listed in
``BENCH_MANIFEST`` (printed at the end of every run, or alone with
``--list-bench``).
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import std_args
from repro.launch.compile_cache import enable_compile_cache

# Which committed perf record each benchmark module refreshes.  CI's
# bench-smoke job runs every one of these with --smoke and uploads
# results/BENCH_*.json as artifacts; committed copies track the perf
# trajectory in-repo.
BENCH_MANIFEST = (
    ("results/BENCH_engine.json",
     "python -m benchmarks.engine_throughput"),
    ("results/BENCH_driver.json",
     "python -m benchmarks.engine_throughput  (same run)"),
    ("results/BENCH_backends.json",
     "python -m benchmarks.backend_comparison"),
    ("results/BENCH_ivf_kernel.json",
     "python -m benchmarks.backend_comparison --ivf-kernel"),
    ("results/BENCH_pq.json",
     "python -m benchmarks.backend_comparison --pq"),
    ("results/BENCH_http.json",
     "python -m benchmarks.http_load"),
    ("results/BENCH_obs.json",
     "python -m benchmarks.obs_overhead"),
)


def print_bench_manifest() -> None:
    root = os.path.join(os.path.dirname(__file__), "..")
    print("# BENCH records refreshed by the benchmark suite "
          "(all accept --smoke):")
    for rel, cmd in BENCH_MANIFEST:
        present = "present" if os.path.exists(os.path.join(root, rel)) \
            else "MISSING"
        print(f"#   {rel:<32} <- {cmd}   [{present}]")


def main() -> None:
    enable_compile_cache()
    ap = std_args(__doc__)
    ap.add_argument("--list-bench", action="store_true",
                    help="list the BENCH_*.json records the suite refreshes "
                         "(and which module writes each), then exit")
    args = ap.parse_args()
    if args.list_bench:
        print_bench_manifest()
        return
    t0 = time.time()

    from benchmarks import (fig3_scatter, table2_truncated_gte,
                            table3_progressive_gte, table4_truncated_openai,
                            table5_progressive_openai)

    print(f"=== corpus: docs={args.docs} dim={args.dim} "
          f"queries={args.queries} runs={args.runs} full={args.full} ===\n")

    table2_truncated_gte.run(args)
    table3_progressive_gte.run(args)
    table4_truncated_openai.run(args)
    table5_progressive_openai.run(args)
    fig3_scatter.run(args)

    # kernel validation micro-bench (interpret mode: correctness + call cost)
    print("# kernel_validation (interpret mode, CPU)")
    print("name,us_per_call,max_err_vs_ref")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.kernels.distance_topk import l2_topk
    from repro.kernels import ref as kref
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    db = jnp.asarray(rng.normal(size=(2048, 64)), jnp.float32)
    t1 = time.perf_counter()
    s, i = l2_topk(q, db, k=8, block_q=32, block_n=256, interpret=True)
    jax.block_until_ready(s)
    us = (time.perf_counter() - t1) * 1e6
    rs, ri = kref.l2_topk_ref(q, db, 8)
    err = float(jnp.abs(s - rs).max())
    print(f"distance_topk,{us:.0f},{err:.2e}")
    print()

    # roofline summary from the dry-run artifacts, if present
    outdir = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    if os.path.isdir(outdir) and os.listdir(outdir):
        print("# roofline (single-pod 16x16, from dry-run artifacts)")
        from benchmarks import roofline
        roofline.report(outdir, "single")

    print()
    print_bench_manifest()
    print(f"\n=== benchmarks done in {time.time() - t0:.1f}s ===")


if __name__ == "__main__":
    main()
