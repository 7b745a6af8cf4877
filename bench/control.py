#!/usr/bin/env python3
"""Readings of the control that ``correct`` has to refuse.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \\
        [--as '{"stage0_bits": 8}' ...]

The control is the configuration's plain reference computed one precision
below what the configuration states, as its ``control`` block says (bfloat16
for float32; a coarser integer grid for the stage-0 prefixes), put in the
program's place: for each seed it answers every query of the cell's pool,
and those answers are compared with the float32 reference exactly as a run
compares the program's.  Each ``--as`` reads another block in its place (a
stage 0 alone one step below, say).  One JSON line per seed and block gives
the numbers beside the configuration's limits; the exit code is 0 when
every line is refused.  The benchmark's own runs never run it.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def readings(c, seed: int, blocks, rundir: str):
    """The compared numbers of each control block for one seed of cell
    ``c``."""
    from harness import cell, check

    corpus, queries, sources, _, _ = cell.pool(c, seed, rundir)
    k = int(c.traffic["k"])
    out = []
    for block in blocks:
        low = cell.reference(c.config, corpus, k, control=block)
        _, ids, scores = low.search(queries)
        del low
        out.append({"status": np.full(len(queries), 200), "ids": ids,
                    "qidx": np.arange(len(queries)), "scores": scores})
    ref = cell.reference(c.config, corpus, k)
    _, prog_ids, _ = ref.search(queries)
    return [check.numbers(served, queries, sources, ref, prog_ids,
                          corpus.n_docs) for served in out]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--as", dest="blocks", action="append", type=json.loads,
                    help="a control block (JSON) read in place of the "
                         "configuration's own; may be given again")
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (tests at small sizes)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, SRC]
    from harness import cell, check, spec

    c = spec.load_cell(args.workload)
    blocks = args.blocks or [c.config["control"]]
    try:
        cell.start(c, not args.cpu, cell.Log())
    except cell.NoChip as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 3
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        rundir = tempfile.mkdtemp(prefix="bench-control-")
        try:
            values = readings(c, seed, blocks, rundir)
        finally:
            shutil.rmtree(rundir, ignore_errors=True)
        for block, v in zip(blocks, values):
            ok, checks = check.judge(v, c.config["correct"])
            failed_all &= not ok
            print(json.dumps({"seed": seed, "control": block,
                              "refused": not ok, "values": v,
                              "checks": checks}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
