"""Paper Table II: accuracy + runtime of Truncated Retrieval vs dimension
(gte-Qwen2-7B-instruct regime: synthetic corpus calibrated to its curve).

Also reproduces the §III.C PCA-vs-truncation comparison that led the paper
to choose truncation.
"""


from benchmarks.common import load_corpus, print_csv, std_args, truncated_row
from repro.launch.compile_cache import enable_compile_cache

PAPER_GTE = {16: 6.56, 32: 39.55, 64: 78.42, 128: 88.79, 256: 92.79,
             512: 93.81, 1024: 94.49, 2048: 94.82, 3072: 94.98, 3584: 95.02}


def run(args=None):
    args = args or std_args(__doc__).parse_args([])
    db, q, gt = load_corpus(args)
    d_full = db.shape[1]
    dims = [d for d in (16, 32, 64, 128, 256, 512, 1024, 2048, 3584)
            if d <= d_full]
    rows = []
    for d in dims:
        r = truncated_row(q, db, gt, d, args.runs)
        r["paper_acc"] = PAPER_GTE.get(d, float("nan"))
        rows.append(r)
    print_csv("table2_truncated_gte (synthetic corpus, gte-calibrated)",
              rows, ["dim", "acc", "runtime_s", "paper_acc"])

    # runtime must grow ~linearly in dim (paper: "Run-Time ... is linear")
    ts = [r["runtime_s"] for r in rows]
    assert ts[-1] > ts[0], "runtime should grow with dim"

    # PCA vs truncation (paper §III.C: truncation slightly better, cheaper)
    from repro.core import fit_pca_power, pca_transform, truncated_search, top1_accuracy
    k = min(128, d_full)
    st = fit_pca_power(db, k, n_iter=6)
    db_p, q_p = pca_transform(st, db), pca_transform(st, q)
    pca_rows = []
    for d in [x for x in (32, 64, 128) if x <= k]:
        _, it = truncated_search(q, db, dim=d, k=1)
        _, ip = truncated_search(q_p, db_p, dim=d, k=1)
        pca_rows.append({
            "dim": d,
            "trunc_acc": float(top1_accuracy(it, gt)) * 100,
            "pca_acc": float(top1_accuracy(ip, gt)) * 100,
        })
    print_csv("table2b_pca_vs_truncation", pca_rows,
              ["dim", "trunc_acc", "pca_acc"])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run(std_args(__doc__).parse_args())
