"""Paper Table IV: truncated retrieval, text-embedding-3-large regime
(3072 dims; steeper Matryoshka-style spectrum: OpenAI trains explicit
truncation points, so low dims carry relatively more signal)."""

from benchmarks.common import load_corpus, print_csv, std_args, truncated_row
from repro.launch.compile_cache import enable_compile_cache

PAPER_OPENAI = {16: 3.32, 32: 29.35, 64: 70.73, 128: 88.18, 256: 92.02,
                512: 93.40, 1024: 93.85, 2048: 94.17, 3072: 94.45}


def run(args=None):
    args = args or std_args(__doc__).parse_args([])
    d = 3072 if args.full else max(args.dim * 3 // 4, 128)
    db, q, gt = load_corpus(args, dim=d, alpha=0.28, sigma=1.45,
                            sigma_spread=0.5)
    dims = [x for x in (16, 32, 64, 128, 256, 512, 1024, 2048, 3072)
            if x <= d]
    rows = []
    for dim in dims:
        r = truncated_row(q, db, gt, dim, args.runs)
        r["paper_acc"] = PAPER_OPENAI.get(dim, float("nan"))
        rows.append(r)
    print_csv("table4_truncated_openai (synthetic, openai-calibrated)",
              rows, ["dim", "acc", "runtime_s", "paper_acc"])
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run(std_args(__doc__).parse_args())
