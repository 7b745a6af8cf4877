#!/usr/bin/env python3
"""Readings of the timed path with a fault planted in it, at a cell's size.

    python3 bench/faults.py --workload <cell> --seeds 21,22,23 \\
        --faults none,probe_one_list,wrong_lists --seconds 20

For each seed: one set-up; then one window of the cell's own traffic for
each fault (``none`` is the sound program, the others are named in
``harness/faults.py``), the fault planted in the served engine before the
window and mended after it; then, with the engine freed, the reference once.
One JSON line per seed and fault gives the compared numbers beside the
configuration's limits: sound readings set a limit's lower end, a fault's
its upper.  The benchmark's own runs never run it.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def readings(c, seed: int, faults, seconds: float, dev):
    """{fault: compared numbers} for one seed of cell ``c``."""
    from harness import cell, check, stats
    from harness.faults import PLANTED

    rundir = tempfile.mkdtemp(prefix="bench-faults-")
    try:
        corpus, queries, sources, order, pool_path = cell.pool(c, seed,
                                                               rundir)
        recs = {}
        with cell.Served(c.config, corpus) as served:
            for name in faults:
                mend = PLANTED[name](served.engine) if name != "none" \
                    else None
                sub = os.path.join(rundir, name)
                os.makedirs(sub)
                clients = cell.Clients(cell.plans(
                    c.traffic, seed, seconds, order, pool_path, sub))
                try:
                    w = cell.measure(served, clients, seconds, False,
                                     c.traffic, sub, dev)
                    recs[name] = (clients.records(), w["t0"], w["t1"])
                finally:
                    clients.close()
                if mend is not None:
                    mend()
        ref = cell.reference(c.config, corpus, int(c.traffic["k"]))
        exact_ids, prog_ids, _ = ref.search(queries)
        out = {}
        for name, (rec, t0, t1) in recs.items():
            out[name] = check.numbers(rec, queries, sources, ref, prog_ids,
                                      corpus.n_docs)
            due = stats.in_window(rec, t0, t1)
            out[name]["recall_at_10"] = check.recall(
                rec["ids"][due], rec["status"][due],
                exact_ids[rec["qidx"][due]])
        del ref
        return out
    finally:
        shutil.rmtree(rundir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="none")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (tests at small sizes)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, SRC]
    from harness import cell, check, spec
    from harness.faults import PLANTED

    c = spec.load_cell(args.workload)
    faults = args.faults.split(",")
    unknown = [f for f in faults if f != "none" and f not in PLANTED]
    if unknown:
        print(f"bench/faults.py: unknown faults {unknown}; known: "
              f"{sorted(PLANTED)}", file=sys.stderr)
        return 2
    try:
        devs = cell.start(c, not args.cpu, cell.Log())
    except cell.NoChip as e:
        print(f"bench/faults.py: {e}", file=sys.stderr)
        return 3
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = readings(c, seed, faults, args.seconds, devs[0])
        except Exception:          # one seed's failure leaves the others
            traceback.print_exc()
            rc = 1
            continue
        for name, values in out.items():
            ok, checks = check.judge(values, c.config["correct"])
            print(json.dumps({"seed": seed, "fault": name, "correct": ok,
                              "values": values, "checks": checks}),
                  flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
