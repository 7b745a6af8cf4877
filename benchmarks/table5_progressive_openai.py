"""Paper Table V: truncated vs progressive, text-embedding-3-large regime."""

from benchmarks.common import (clamp_configs, load_corpus, print_csv,
                               progressive_row, std_args, truncated_row)
from repro.core import build_index, make_schedule, stage_dims
from repro.launch.compile_cache import enable_compile_cache


def configs_for(d_full: int):
    if d_full >= 3072:
        return [(256, (128, 256, 128)), (512, (256, 512, 16)),
                (1024, (128, 2048, 32)), (2048, (128, 3072, 64)),
                (3072, (256, 3072, 64))]
    grid = [(96, (48, 96, 128)), (192, (96, 192, 64)),
            (d_full // 2, (96, d_full // 2, 128)),
            (d_full, (96, d_full, 128)),
            (d_full, (d_full // 2, d_full, 64))]
    return clamp_configs(grid, d_full)


def run(args=None):
    args = args or std_args(__doc__).parse_args([])
    d = 3072 if args.full else max(args.dim * 3 // 4, 128)
    db, q, gt = load_corpus(args, dim=d, alpha=0.28, sigma=1.45,
                            sigma_spread=0.5)
    rows = []
    for trunc_dim, (ds, dm, k0) in configs_for(d):
        tr = truncated_row(q, db, gt, trunc_dim, args.runs)
        sched = make_schedule(ds, dm, k0)
        idx = build_index(db, stage_dims(sched))
        pr = progressive_row(q, db, gt, ds, dm, k0, args.runs,
                             index=idx, dims=stage_dims(sched))
        rows.append({
            "trunc_dim": trunc_dim, "trunc_acc": tr["acc"],
            "trunc_runtime_s": tr["runtime_s"],
            "prog_config": f"({ds};{dm};{k0})",
            "prog_acc": pr["acc"], "prog_runtime_s": pr["runtime_s"],
            "speedup": tr["runtime_s"] / max(pr["runtime_s"], 1e-9),
        })
    print_csv("table5_trunc_vs_progressive_openai", rows,
              ["trunc_dim", "trunc_acc", "trunc_runtime_s", "prog_config",
               "prog_acc", "prog_runtime_s", "speedup"])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run(std_args(__doc__).parse_args())
