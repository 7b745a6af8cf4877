"""Fused PQ ADC scan Pallas TPU kernel: LUT-resident stage 0 at M bytes/row.

The PQ stage-0 scan in XLA is a per-subspace gather chain: the (Q, M, C)
lookup tables materialize, then M (Q, N) gathered score planes are summed
and written back for ``top_k`` — all HBM round trips proportional to N.
This kernel keeps the per-query **(M, C) ADC lookup table resident in
VMEM** for the whole scan and streams only the uint8 code slabs:

* Code slabs ((block_m, M) uint8) stream HBM→VMEM via the same
  auto-double-buffered block pipeline as `ivf_scan` — M bytes per row, the
  4–8× compression step past the int8 member slabs.
* In-VMEM table lookup is a **one-hot contraction**: TPUs have no fast
  VMEM gather, but ``codes[:, m] == iota(C)`` builds a (block_m, C)
  one-hot per subspace that contracts with LUT row m on the MXU; the M
  (1, block_m) products sum to the ADC score row.
* Padding and tombstones are masked in-kernel via the caller-masked id
  table (-1 ids score +inf; per query in the list-major scan, see
  `repro.kernels.ivf_scan.probed_ids`), and the running top-k rides in
  VMEM scratch (merged by `distance_topk.merge_topk`); only the final
  (Q, k) result ever reaches HBM.

Two grid shapes share the kernel body:

* `pq_scan_topk` — **flat**: the whole (N, M) code block, chunked.  Backs
  ``QuantizedProgressiveBackend(codec='pq', use_kernel=...)``.
* `pq_ivf_scan_topk` — **list-major**: scalar-prefetched probe table
  drives dynamic BlockSpec index maps over `pack_ivf_lists(dtype='pq')`
  slabs, so IVF-PQ is one fused probe+LUT-scan program.  Backs
  ``IVFProgressiveBackend(stage0_dtype='pq')``.

Validated against `repro.kernels.ref.pq_scan_ref` / `pq_ivf_scan_ref` and
the XLA `pq_progressive_search` path in interpret mode, and compiled for a
TPU v5e in `tests/test_tpu_compile.py`.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance_topk import merge_topk, sort_topk

Array = jax.Array


def _pq_body(lut_ref, codes_ref, ids_ref, out_s_ref, out_i_ref,
             best_s, best_i):
    """Score one (block_m, M) code slab against the resident LUT."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, jnp.inf)
        best_i[...] = jnp.full_like(best_i, -1)

    lut = lut_ref[...]                               # (M, C) f32
    m, c = lut.shape
    codes = codes_ref[...].astype(jnp.int32)         # (bm, M)
    bm = codes.shape[0]
    # one-hot contraction: the TPU-native LUT gather.  For subspace m,
    # hot[r, c] selects row r's code; contracting it against the LUT row
    # on the MXU yields that subspace's table entry for every row, and the
    # M partial rows sum to the ADC score row.
    entry = jax.lax.broadcasted_iota(jnp.int32, (bm, c), 1)
    scores = jnp.zeros((1, bm), jnp.float32)
    for sub in range(m):
        hot = (codes[:, sub:sub + 1] == entry).astype(jnp.float32)
        scores = scores + jax.lax.dot_general(
            lut[sub:sub + 1, :], hot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # (1, bm)
    ids = ids_ref[...]
    # -1 ids are padding or tombstoned rows: unreturnable
    scores = jnp.where(ids >= 0, scores, jnp.inf)
    best_s[...], best_i[...] = merge_topk(best_s[...], best_i[...],
                                          scores, ids)

    @pl.when(j == nj - 1)
    def _flush():
        out_s_ref[...], out_i_ref[...] = sort_topk(best_s[...], best_i[...])


def _pq_call(body, grid_spec, nq, k, interpret, *args):
    """Shared pallas_call of both grid shapes: (Q, 1, k) outputs, squeezed
    per query (see `repro.kernels.ivf_scan._ivf_scan_call`)."""
    out_s, out_i = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*args)
    return out_s[:, 0], out_i[:, 0]


def _scratch(k):
    return [pltpu.MemorySpace.VMEM((1, k), jnp.float32),
            pltpu.MemorySpace.VMEM((1, k), jnp.int32)]


@functools.partial(jax.jit, static_argnames=("k", "block_m", "interpret"))
def _pq_scan_call(lut, codes, ids, *, k, block_m, interpret):
    nq, m, c = lut.shape
    nj = codes.shape[0] // block_m
    sqz = pl.squeezed
    out_spec = pl.BlockSpec((sqz, 1, k), lambda i, j: (i, 0, 0))
    grid_spec = pl.GridSpec(
        grid=(nq, nj),
        in_specs=[
            pl.BlockSpec((sqz, m, c), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((block_m, m), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=[out_spec, out_spec],
        scratch_shapes=_scratch(k),
    )
    return _pq_call(_pq_body, grid_spec, nq, k, interpret, lut, codes, ids)


def pq_scan_topk(
    lut: Array,
    codes: Array,
    ids: Array,
    *,
    k: int,
    block_m: int = 128,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """Fused flat ADC scan: score every coded row, keep the best k.

    Args:
      lut:       (Q, M, C) per-query ADC tables (`repro.core.pq.pq_lut`).
      codes:     (N, M) uint8 PQ codes.
      ids:       (N,) int32 global doc ids with every unreturnable row
                 already masked to -1 (tombstones, rows past the coded
                 prefix); live rows carry their own index.
      k:         neighbours kept (static).
      interpret: interpret mode for CPU validation.

    Returns:
      ((Q, k) float32 rank-equivalent ADC scores ascending, +inf at empty
      slots; (Q, k) int32 global doc ids, -1 at empty slots).
    """
    nq = lut.shape[0]
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    n = codes.shape[0]
    bm = min(int(block_m), max(n, 1))
    pad = -n % bm
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    return _pq_scan_call(
        lut.astype(jnp.float32), codes, ids[None, :].astype(jnp.int32),
        k=k, block_m=bm, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("k", "max_len", "block_m", "interpret"))
def _pq_ivf_call(lut, probe, codes, ids, *, k, max_len, block_m,
                 interpret):
    nq, m, c = lut.shape
    n_probe = probe.shape[1]
    nc = max_len // block_m
    nj = n_probe * nc
    sqz = pl.squeezed

    def codes_idx(i, j, probe):
        return (probe[i, j // nc] * nc + j % nc, 0)

    def probed_idx(i, j, probe):
        return (i * n_probe + j // nc, 0, j % nc)

    def kern(probe_ref, *args):
        _pq_body(*args)

    out_spec = pl.BlockSpec((sqz, 1, k), lambda i, j, probe: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nq, nj),
        in_specs=[
            pl.BlockSpec((sqz, m, c), lambda i, j, probe: (i, 0, 0)),
            pl.BlockSpec((block_m, m), codes_idx),
            pl.BlockSpec((sqz, 1, block_m), probed_idx),
        ],
        out_specs=[out_spec, out_spec],
        scratch_shapes=_scratch(k),
    )
    return _pq_call(kern, grid_spec, nq, k, interpret, probe, lut, codes,
                    ids.reshape(nq * n_probe, 1, max_len))


def pq_ivf_scan_topk(
    q: Array,
    probe: Array,
    member_ids: Array,
    pack: Dict,
    *,
    k: int,
    interpret: bool = False,
    lut: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Fused IVF-PQ stage 0: probe-driven LUT scan over list-major codes.

    The list-major twin of `repro.kernels.ivf_scan.ivf_scan_topk`: same
    scalar-prefetched probe table, same per-query id table, same
    double-buffered slab streaming, same in-VMEM top-k — but the member
    slabs hold PQ codes (`pack_ivf_lists(dtype='pq')`) and scoring is the
    resident-LUT one-hot contraction instead of a distance matmul.

    Args:
      q:          (Q, D) queries (only ``[:, :pack['dim']]`` feeds the LUT;
                  ignored when ``lut`` is given).
      probe:      (Q, n_probe) int32 probed list indices (distinct per row).
      member_ids: (Q, n_probe, max_len) int32 per-query id table
                  (`repro.kernels.ivf_scan.probed_ids`), every unreturnable
                  slot masked to -1 (padding AND tombstones); or a
                  pre-masked (n_lists, max_len) member table, whose probed
                  rows are gathered here.
      pack:       `pack_ivf_lists(..., dtype='pq')` output.
      k:          neighbours kept (static).
      interpret:  interpret mode for CPU validation.
      lut:        optional precomputed (Q, M, C) ADC tables.

    Returns:
      ((Q, k) float32 ADC scores ascending, +inf empties;
       (Q, k) int32 global doc ids, -1 empties).
    """
    from repro.core.pq import pq_lut
    from repro.kernels.ivf_scan import _padded_ids

    if pack["dtype"] != "pq":
        raise ValueError(
            f"pq_ivf_scan_topk needs a dtype='pq' pack, got "
            f"{pack['dtype']!r} (use ivf_scan_topk)")
    max_len, bm = pack["max_len"], pack["block_m"]
    if lut is None:
        d0 = pack["dim"]
        lut = pq_lut(q[:, :d0], pack["codebooks"], pack["cent_sq"])
    nq = lut.shape[0]
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    ids = _padded_ids(member_ids, probe, max_len)
    return _pq_ivf_call(
        lut.astype(jnp.float32), probe.astype(jnp.int32), pack["rows"],
        ids, k=k, max_len=max_len, block_m=bm, interpret=interpret)


def flat_stage0_bytes_model(
    *,
    n: int,
    k: int,
    row_bytes: float,
    lut_bytes: float = 0.0,
) -> Dict[str, float]:
    """Modeled per-query stage-0 HBM bytes for a *flat* coded scan.

    The full-scan twin of `repro.kernels.ivf_scan.stage0_bytes_model`, for
    the quantized backend's code-block stage 0 (int8: ``row_bytes = Ds``;
    PQ: ``row_bytes = M`` plus the ``lut_bytes`` per-query table):

      XLA   : read the code block once (``row_bytes``/row), write + re-read
              the (N,) f32 score row for ``top_k``, plus the LUT round trip
              (PQ only — XLA materializes it too).
      fused : stream the code block once, the (N,) masked id table, the
              LUT read (it stays VMEM-resident thereafter), and the (k,)
              result.
    """
    n = float(n)
    xla = row_bytes * n + 2 * 4 * n + lut_bytes
    fused = row_bytes * n + 4 * n + lut_bytes + 8 * k
    return {"xla_bytes": xla, "fused_bytes": fused,
            "ratio": fused / xla if xla else 0.0}
