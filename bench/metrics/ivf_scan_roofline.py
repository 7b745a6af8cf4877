"""Share of its roofline that the fused IVF stage-0 kernel reaches: the
least time the chip could take for the kernel's work (bytes over peak HBM
bandwidth, or operations over peak bf16 FLOP/s, whichever is larger), over
the kernel's device time in the trace.  The work is counted from the packed
slab shapes and the buckets dispatched while tracing."""

from harness import kernels

# the kernel's operations in the device trace: the Pallas custom call of
# ``_ivf_scan_call`` (one per dispatch), named by its HLO instruction
PATTERN = ("%_ivf_scan_call", "custom-call(")


def read(ctx):
    if ctx.trace is None or ctx.kernel is None or ctx.peaks is None:
        return None
    calls, seconds = ctx.trace.op_time(*PATTERN)
    counts = ctx.traced["bucket_counts"]
    if not calls or seconds <= 0 or not counts:
        return None
    rows = sum(b * n for b, n in counts.items()) / sum(counts.values())
    cost = kernels.ivf_scan_cost(queries=rows * calls, **ctx.kernel)
    return 100.0 * kernels.least_seconds(cost, ctx.peaks)[0] / seconds
