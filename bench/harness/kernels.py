"""Operations and bytes of the kernels on the served path, from shapes.

``ivf_scan_cost`` counts the fused IVF stage-0 kernel
(``repro/kernels/ivf_scan.py``): for each query it streams the member slabs
of ``n_probe`` lists once (``max_len`` rows each, ``member_bytes`` per
dimension), with the lists' id and norm side tables (4 bytes a row each),
reads the query's ``d0`` float32 once, and writes ``k`` scores and ids.  The
multiply-adds are ``n_probe * max_len * d0`` per query.  This is the fused
branch of the program's ``stage0_bytes_model``, plus the query read.
"""

from __future__ import annotations

from typing import Dict, Tuple


def ivf_scan_cost(*, queries: float, n_probe: int, max_len: int, d0: int,
                  k: int, member_bytes: int) -> Dict[str, float]:
    rows = float(n_probe * max_len)
    per_query = (member_bytes * d0 * rows    # member slabs, one pass
                 + 4 * rows                  # masked id table
                 + 4 * rows                  # packed norms
                 + 4 * d0                    # the query
                 + 8 * k)                    # top-k scores and ids out
    return {"flops": queries * 2.0 * rows * d0,
            "bytes": queries * per_query}


def least_seconds(cost: Dict[str, float], peaks: Dict
                  ) -> Tuple[float, str]:
    """(least seconds the chip could take, the bound that sets it)."""
    t_mem = cost["bytes"] / float(peaks["hbm_bytes_per_s"])
    t_ops = cost["flops"] / float(peaks["bf16_flops_per_s"])
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
