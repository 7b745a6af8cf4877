"""Share of its roofline that the flat stage 0 reaches: the least time the
chip could take for the stage-0 work of the dispatches enqueued in the
traced window, over the device time under the program's ``stage0`` named
scope there.

The least time of one dispatch of bucket ``b`` is the larger of bytes over
peak HBM bandwidth and operations over peak bf16 FLOP/s, with
``bytes = n_docs * (2 * d0 + 5)`` (the d0-wide prefix of every row at
2 bytes an element, a 4-byte norm and a 1-byte validity bit) and
``flops = 2 * n_docs * d0 * b``.  Two bytes an element because the stated
precision of stage 0 is one bfloat16 pass: a store that keeps a bfloat16
prefix does the same work, and counting the float32 rows would let a
faithful change read over 100%."""

from harness import kernels, scopes


def stage0_cost(n_docs: int, d0: int, bucket: int):
    return {"bytes": n_docs * (2 * d0 + 5), "flops": 2 * n_docs * d0 * bucket}


def read(ctx):
    cfg = ctx.cell.config
    if ctx.peaks is None or cfg["engine"]["backend"]["backend"] != "flat":
        return None
    found = scopes.per_dispatch(ctx, "/stage0/")
    if found is None or found[0] <= 0:
        return None
    seconds, dispatches = found
    n_docs, d0 = int(cfg["n_docs"]), int(cfg["schedule"]["d_start"])
    least = sum(kernels.least_seconds(stage0_cost(n_docs, d0, b),
                                      ctx.peaks)[0] for b in dispatches)
    return 100.0 * least / seconds
