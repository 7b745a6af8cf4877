#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, then a short window at each
offered rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 200,400,800

For each rate it prints one JSON line: the offered and completed rates, the
p50 and p95 latency from due time, the generator's lateness, and the mean
latency of the window's last quarter over its first (above 1 the driver's
queue grew through the window).  The knee is the highest rate that completes
what it offers without a growing queue; the cell's traffic file then offers
about four fifths of it.  The set-up is the cell's own, so the sweep needs
the chip the cell needs.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def summary(rec, t0, t1, rate, window):
    import numpy as np

    from harness import stats

    due = stats.in_window(rec, t0, t1)
    lat = stats.latency_ms(rec)[due]
    t = rec["due"][due] - t0
    q = (t1 - t0) / 4
    first, last = lat[t < q], lat[t >= 3 * q]
    return {"offered_per_s": rate,
            "completed_per_s": stats.completed_rate(rec, t0, t1),
            "p50_ms": stats.percentile(lat, 50),
            "p95_ms": stats.percentile(lat, 95),
            "lateness_p95_ms": stats.percentile(stats.lateness_ms(rec), 95),
            "growth": float(np.mean(last) / np.mean(first)),
            "failed": int(np.count_nonzero(rec["status"][due] != 200)),
            "batches": window["n_batches"],
            "compiles": window["n_compiles"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, searches/s")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, SRC]
    from harness import cell, spec

    c = spec.load_cell(args.workload)
    if c.traffic["arrivals"] != "poisson":
        print("bench/sweep.py: only open-loop cells have a knee",
              file=sys.stderr)
        return 2
    log = cell.Log()
    try:
        devs = cell.start(c, True, log)
    except cell.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 3
    rundir = tempfile.mkdtemp(prefix="bench-sweep-")
    try:
        corpus, _, _, order, pool_path = cell.pool(c, args.seed, rundir)
        with cell.Served(c.config, corpus) as served:
            log(f"set-up {time.monotonic() - T_START:.1f}s")
            for i, rate in enumerate(float(r) for r in args.rates.split(",")):
                sub = os.path.join(rundir, f"rate{i}")
                os.makedirs(sub)
                traffic = dict(c.traffic, rate_per_s=rate)
                clients = cell.Clients(cell.plans(
                    traffic, args.seed, args.seconds, order, pool_path, sub))
                try:
                    w = cell.measure(served, clients, args.seconds, False,
                                     traffic, sub, devs[0])
                    rec = clients.records()
                finally:
                    clients.close()
                print(json.dumps(summary(rec, w["t0"], w["t1"], rate,
                                         w["window"])), flush=True)
    finally:
        shutil.rmtree(rundir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
