"""Share of its roofline that the flat PQ stage-0 kernel reaches: the least
time the chip could take for the kernel's work (bytes over peak HBM
bandwidth, or operations over peak bf16 FLOP/s, whichever is larger), over
the device time of the kernel's calls in the trace.

The work of one dispatch of bucket ``b`` over ``n_docs`` coded rows of
``m`` uint8 codes, ``codes`` centroids a subspace, keeping ``k`` survivors:

  bytes = n_docs * (m + 4)       the codes and the masked id table, read once
        + b * m * codes * 4      the float32 ADC tables
        + b * 8 * k              the survivors' scores and ids out
  flops = 2 * b * n_docs * m     one table entry added per row and subspace

This is the same work whatever implements the scan, so a kernel that streams
the codes once a dispatch reads higher and no faithful change reads over
100%.  The shapes come from the configuration (the program's state carries
no kernel pack for the flat scan), and the mean bucket from the engine's
counters over the trace."""

from harness import kernels

# the kernel's operations in the device trace: the Pallas custom call of
# ``_pq_scan_call`` (one per dispatch), named by its HLO instruction
PATTERN = ("%_pq_scan_call", "custom-call(")


def pq_scan_cost(*, n_docs: int, m: int, codes: int, k: int, bucket: float):
    return {"bytes": n_docs * (m + 4) + bucket * m * codes * 4
            + bucket * 8 * k,
            "flops": 2.0 * bucket * n_docs * m}


def shapes(cfg):
    """(n_docs, m, codes, k) of the configuration's PQ stage 0, which states
    ``pq_m``, ``pq_codes`` and ``pq_oversample``; None for another
    backend."""
    b = cfg["engine"]["backend"]
    if b.get("backend") != "quantized" or b.get("codec") != "pq":
        return None
    n_docs = int(cfg["n_docs"])
    k = min(int(cfg["schedule"]["k0"]) * int(b["pq_oversample"]), n_docs)
    return n_docs, int(b["pq_m"]), int(b["pq_codes"]), k


def read(ctx):
    found = shapes(ctx.cell.config)
    if ctx.trace is None or ctx.peaks is None or found is None:
        return None
    calls, seconds = ctx.trace.op_time(*PATTERN)
    counts = (ctx.traced or {}).get("bucket_counts")
    if not calls or seconds <= 0 or not counts:
        return None
    n_docs, m, codes, k = found
    bucket = sum(b * n for b, n in counts.items()) / sum(counts.values())
    cost = pq_scan_cost(n_docs=n_docs, m=m, codes=codes, k=k, bucket=bucket)
    least = calls * kernels.least_seconds(cost, ctx.peaks)[0]
    return 100.0 * least / seconds
