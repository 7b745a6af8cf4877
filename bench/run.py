#!/usr/bin/env python3
"""Benchmark of progressive search served over HTTP on a TPU.

Run from the root of a checkout, on a machine with the chips the cell asks
for:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cells; each is a configuration (a deployment,
``bench/configs/``) under a traffic mix (``bench/traffic/``).  One process
holds the chip and serves; load generator processes that never import JAX
send the traffic.  A configuration with ``"replicas": N`` is served by N
one-chip replicas behind the program's router: this process is replica 0
on chip 0, each other replica a process of its own on one chip
(``bench/harness/replica.py``), and the router the program's own launcher
(``python -m repro.launch.serve --serve-http --role router``) in a process
that holds no chip; the load generators send to the router.

The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, last,
``checks``: each number compared with the plain reference beside its limit.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of part of the window.

Without a TPU (or with fewer chips than the cell asks for), or outside a
checkout that holds ``src/repro``, it exits nonzero and prints no result.
JAX's persistent compilation cache is kept in ``.jax_cache/`` at the
checkout root, or where ``JAX_COMPILATION_CACHE_DIR`` points.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench/run.py: no repro package under {SRC}; run it from the "
              f"root of a checkout", file=sys.stderr)
        return 2
    # libtpu logs to a fixed directory under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, SRC]
    from harness import cell, spec

    try:
        c = spec.load_cell(args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    try:
        result = cell.run(c, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    except cell.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
