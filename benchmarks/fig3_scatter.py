"""Paper Fig. 3/4: full accuracy-vs-runtime scatter of both methods.

Emits a CSV of (method, config, acc, runtime) points and the headline
statistic: the fraction of progressive configurations that dominate the
truncated frontier (above the accuracy-for-time curve), plus the pooled
(paper-faithful) vs per-query variant comparison."""


from benchmarks.common import (load_corpus, print_csv, progressive_row,
                               std_args, timed_median, truncated_row)
from repro.core import (make_schedule, progressive_search_pooled,
                        top1_accuracy)
from repro.launch.compile_cache import enable_compile_cache


def run(args=None):
    args = args or std_args(__doc__).parse_args([])
    db, q, gt = load_corpus(args)
    d_full = db.shape[1]

    trunc_dims = [d for d in (16, 32, 64, 96, 128, 192, 256, 384, 512,
                              768, 1024, 2048, 3584) if d <= d_full]
    rows = []
    for d in trunc_dims:
        r = truncated_row(q, db, gt, d, args.runs)
        rows.append({"method": "truncated", "config": f"d={d}",
                     "acc": r["acc"], "runtime_s": r["runtime_s"]})

    d_starts = [d for d in (32, 64, 128, 256) if d < d_full]
    k0s = (4, 16, 64, 128)
    d_maxes = [d for d in (128, 256, 512, 1024, 3584) if d <= d_full]
    prog_rows = []
    for ds in d_starts:
        for dm in d_maxes:
            if dm <= ds:
                continue
            for k0 in k0s:
                r = progressive_row(q, db, gt, ds, dm, k0, args.runs)
                prog_rows.append({
                    "method": "progressive", "config": f"({ds};{dm};{k0})",
                    "acc": r["acc"], "runtime_s": r["runtime_s"]})
    rows += prog_rows
    print_csv("fig3_scatter_points", rows,
              ["method", "config", "acc", "runtime_s"])

    # dominance statistic: progressive point dominates if some truncated
    # point is both slower and less accurate... we report the paper's
    # reading: for each progressive point, accuracy vs the truncated point
    # of equal-or-greater runtime.
    tr = [(r["runtime_s"], r["acc"]) for r in rows if r["method"] == "truncated"]
    tr.sort()
    def frontier_acc(t):
        best = 0.0
        for rt, acc in tr:
            if rt <= t:
                best = max(best, acc)
        return best
    above = sum(1 for r in prog_rows if r["acc"] >= frontier_acc(r["runtime_s"]))
    print(f"# progressive points at-or-above the truncated frontier: "
          f"{above}/{len(prog_rows)}")

    # pooled (paper-faithful) vs per-query variant at one config
    ds, dm, k0 = d_starts[0], d_maxes[-1], 16
    sched = make_schedule(ds, dm, k0)
    t_pool, (s, c) = timed_median(
        lambda: progressive_search_pooled(q, db, sched), args.runs)
    acc_pool = float(top1_accuracy(c, gt)) * 100
    pq = [r for r in prog_rows if r["config"] == f"({ds};{dm};{k0})"][0]
    print(f"# pooled-vs-perquery @({ds};{dm};{k0}): pooled acc={acc_pool:.2f} "
          f"t={t_pool:.3f}s | per-query acc={pq['acc']:.2f} "
          f"t={pq['runtime_s']:.3f}s")
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run(std_args(__doc__).parse_args())
