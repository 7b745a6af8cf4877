"""Fused IVF probe+scan Pallas TPU kernel: probed lists stay in VMEM from
gather to top-k.

The IVF stage-0 hot path in XLA is three HBM round trips: the ``lists[probe]``
gather materializes a (Q, n_probe·max_len) candidate-id table, the rescore
gathers every candidate row into a (Q, C, d0) tensor, and the (Q, C) score
matrix is written back out for ``top_k``.  All three are pure memory traffic —
exactly where the RAG surveys put the retrieval bottleneck.  This kernel
collapses them into **one streaming read of the probed lists' member rows**:

* Member vectors are re-packed *list-major* at build time
  (`pack_ivf_lists`): list ``c``'s members occupy the contiguous slab
  ``rows[c·max_len : (c+1)·max_len]`` at the stage-0 dimensionality, so one
  probed list is one contiguous HBM→VMEM block copy — no row-granular
  gather at query time.
* The probe table is **scalar-prefetched** (like `gather_rescore`'s
  candidate ids): BlockSpec index maps read ``probe[i, p]`` before the body
  runs, so Pallas's pipeline machinery double-buffers list ``p+1``'s member
  slab while list ``p`` is being scored.
* Scores are truncated-dim L2 (``‖x‖² − 2 q·x`` on the MXU, f32 accumulate)
  with padding (``-1`` ids) and tombstoned rows masked to +inf in-kernel via
  a per-query id table (`probed_ids`): the probed lists' member ids, masked
  by the live validity bits over those slots alone.
* A running top-k rides in VMEM scratch across the sequential
  (probe × chunk) grid axis, merged by `distance_topk.merge_topk` — only
  the final (Q, k) result ever reaches HBM.

An **int8 member-block variant** composes with `repro.core.quant`: member
slabs are stored as per-dimension-scaled int8 codes (4× less stage-0 HBM
traffic), the query is folded onto the same grid outside the kernel
(``q_eff = round(clip(q/s))·s²``, the `_scaled_space_scores` split), and the
packed norms are the dequantized ones — so the quantized and IVF backends
stop being either/or.

Validated against `repro.kernels.ref.ivf_scan_ref` and the XLA
`ivf_progressive_search_sched` path in interpret mode, and compiled for a
TPU v5e in `tests/test_tpu_compile.py`.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import quant
from repro.kernels.distance_topk import merge_topk, sort_topk

Array = jax.Array


def pack_ivf_lists(
    db: Array,
    lists: Array,
    *,
    dim: int,
    db_sq_at_dim: Optional[Array] = None,
    dtype: str = "float32",
    block_m: int = 128,
    scale: Optional[Array] = None,
    pq_codebooks: Optional[Array] = None,
) -> Dict:
    """Build the list-major member pack the fused kernel scans.

    Args:
      db:           (N, D) corpus rows (HBM snapshot at build time).
      lists:        (n_lists, max_len) int32 member table, -1 padded.
      dim:          stage-0 dimensionality; member slabs store ``[:, :dim]``.
      db_sq_at_dim: optional (N,) precomputed prefix squared norms at ``dim``
                    (the store's cached ``sq_prefix`` column) — passing it
                    keeps the pack's norms bit-identical to the XLA rescore
                    path and skips the O(N·dim) recompute.
      dtype:        'float32' | 'int8' (per-dimension symmetric codes; the
                    packed norms become the *dequantized* ones) | 'pq'
                    (product-quantization codes against ``pq_codebooks``;
                    ADC lookup needs no norm table — ``sq`` is None).
      block_m:      member rows scored per kernel step; ``max_len`` is padded
                    to a multiple.
      scale:        optional (dim,) quantization scale to reuse (int8 only) —
                    lets incremental appends code new rows onto the grid the
                    pack was built with.
      pq_codebooks: (M, C, dim//M) PQ codebooks ('pq' only, required) —
                    trained by the caller on live rows (`repro.core.pq`);
                    stored in the pack so incremental appends encode against
                    the same frozen codebooks.

    Returns:
      dict: ``rows`` (n_lists·max_len_p, dim-or-M) member slabs, ``sq``
      (n_lists, max_len_p) f32 norms (+inf at pads; None for 'pq'),
      ``scale`` (dim,) f32 or None, ``codebooks``/``cent_sq`` ('pq' only),
      plus static meta (``dim``, ``max_len``, ``block_m``, ``dtype``).
    """
    if dtype not in ("float32", "int8", "pq"):
        raise ValueError(f"pack dtype must be float32|int8|pq, got {dtype!r}")
    if dtype == "pq" and pq_codebooks is None:
        raise ValueError("dtype='pq' needs pq_codebooks (see repro.core.pq)")
    n_lists, max_len = lists.shape
    bm = min(int(block_m), max(int(max_len), 1))
    pad = -max_len % bm
    if pad:
        lists = jnp.pad(lists, ((0, 0), (0, pad)), constant_values=-1)
        max_len = max_len + pad
    flat = lists.reshape(-1)
    safe = jnp.maximum(flat, 0)
    rows = db[safe, :dim].astype(jnp.float32)          # (n_lists*max_len, dim)
    member = flat >= 0

    codebooks = cent_sq = None
    if dtype == "int8":
        if scale is None:
            # fit the grid on real member rows only (pad slots repeat row 0)
            scale = quant.fit_int8_scale(rows, member)
        rows, sq = quant.int8_encode(rows, scale)
        sq = jnp.where(member, sq, jnp.inf).reshape(n_lists, max_len)
    elif dtype == "pq":
        from repro.core.pq import pq_cent_sq, pq_encode
        scale, sq = None, None
        codebooks = pq_codebooks
        cent_sq = pq_cent_sq(codebooks)
        rows = pq_encode(rows, codebooks)              # (n_lists*max_len, M)
    else:
        scale = None
        if db_sq_at_dim is not None:
            sq = db_sq_at_dim[safe].astype(jnp.float32)
        else:
            sq = jnp.sum(rows * rows, axis=-1)
        sq = jnp.where(member, sq, jnp.inf).reshape(n_lists, max_len)
    return {
        "rows": rows,
        "sq": sq,
        "scale": scale,
        "codebooks": codebooks,
        "cent_sq": cent_sq,
        "dim": int(dim),
        "max_len": int(max_len),
        "block_m": int(bm),
        "dtype": dtype,
    }


# host-side scatter-batch padding shared with the incremental-append paths
_pad_pow2 = quant.pad_pow2


def update_pack(pack: Dict, db: Array, ids, dests) -> Dict:
    """Write appended rows into the pack's member slabs (incremental IVF).

    ``ids`` are global doc ids, ``dests`` their flat slab positions
    (``list·max_len + slot``).  Returns a new pack dict; int8 packs code
    the new rows with the **stored** scale and 'pq' packs encode against
    the **stored** codebooks, so the grid stays consistent with the built
    slabs.  The scatters are `repro.core.quant.scatter_rows*`: slab
    buffers are donated off-CPU, so XLA updates them in place instead of
    copying the whole O(n_lists·max_len·dim) slab.
    """
    ids = _pad_pow2(np.asarray(ids, np.int32))
    dests = jnp.asarray(_pad_pow2(np.asarray(dests, np.int32)))
    rows = db[jnp.asarray(ids), : pack["dim"]].astype(jnp.float32)
    out = dict(pack)
    if pack["dtype"] == "pq":
        from repro.core.pq import pq_encode
        codes = pq_encode(rows, pack["codebooks"])
        out["rows"] = quant.scatter_rows(pack["rows"], dests, codes)
        return out
    if pack["dtype"] == "int8":
        rows, sq = quant.int8_encode(rows, pack["scale"])
    else:
        sq = jnp.sum(rows * rows, axis=-1)
    new_rows, new_sq = quant.scatter_rows2(
        pack["rows"], pack["sq"].reshape(-1), dests, rows, sq)
    out["rows"] = new_rows
    out["sq"] = new_sq.reshape(pack["sq"].shape)
    return out


def _mask_ids(ids: Array, valid: Array) -> Array:
    """-1 wherever ``ids`` is padding or names a row ``valid`` rules out."""
    return jnp.where((ids >= 0) & valid[jnp.maximum(ids, 0)], ids, -1)


def probed_ids(lists: Array, probe: Array,
               valid: Optional[Array] = None) -> Array:
    """The per-query id table the list-major kernels read.

    Args:
      lists: (n_lists, max_len) int32 member table, -1 padded.
      probe: (Q, n_probe) int32 probed list indices.
      valid: optional (N,) bool live bits of this dispatch (tombstones and
             any filter mask); the packed member *vectors* are a build-time
             snapshot and are not consulted for liveness.

    Returns:
      (Q, n_probe, max_len) int32: row ``[i, p]`` is list ``probe[i, p]``'s
      member ids with every slot ``valid`` rules out set to -1.  The
      validity gather covers the probed slots alone, Q·n_probe·max_len of
      them; where that would reach the whole table (Q·n_probe ≥ n_lists)
      the table is masked once and its probed rows gathered instead.  Both
      orders give the same table.
    """
    if valid is not None and probe.size >= lists.shape[0]:
        return _mask_ids(lists, valid)[probe]
    ids = lists[probe]
    return ids if valid is None else _mask_ids(ids, valid)


def _kernel(
    probe_ref, q_ref, rows_ref, sq_ref, ids_ref, out_s_ref, out_i_ref,
    best_s, best_i,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, jnp.inf)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...]                                     # (1, d0) f32
    # int8 slabs widen in VMEM (exactly): HBM traffic stays one byte per dim
    rows = rows_ref[...].astype(jnp.float32)           # (bm, d0)
    ip = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # (1, bm)
    ids = ids_ref[...]
    scores = sq_ref[...] - 2.0 * ip
    # -1 ids are list padding or tombstoned rows: unreturnable
    scores = jnp.where(ids >= 0, scores, jnp.inf)
    best_s[...], best_i[...] = merge_topk(best_s[...], best_i[...],
                                          scores, ids)

    @pl.when(j == nj - 1)
    def _flush():
        out_s_ref[...], out_i_ref[...] = sort_topk(best_s[...], best_i[...])


@functools.partial(
    jax.jit,
    static_argnames=("k", "dim", "max_len", "block_m", "interpret"),
)
def _ivf_scan_call(
    q, probe, rows, sq, ids, *, k, dim, max_len, block_m, interpret,
):
    nq = q.shape[0]
    n_lists = sq.shape[0]
    n_probe = probe.shape[1]
    nc = max_len // block_m
    nj = n_probe * nc
    sqz = pl.squeezed

    # Per-query and per-list blocks are one row high.  Mosaic tiles the
    # last two block dims by (8, 128) unless they span the array, so each
    # such array carries a unit middle axis and the BlockSpec squeezes its
    # leading one: the kernel sees (1, n) blocks of a (1, n)-wide array.
    def rows_idx(i, j, probe):
        return (probe[i, j // nc] * nc + j % nc, 0)

    def list_idx(i, j, probe):
        return (probe[i, j // nc], 0, j % nc)

    def probed_idx(i, j, probe):
        return (i * n_probe + j // nc, 0, j % nc)

    def query_idx(i, j, probe):
        return (i, 0, 0)

    out_s, out_i = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nq, nj),
            in_specs=[
                pl.BlockSpec((sqz, 1, dim), query_idx),
                pl.BlockSpec((block_m, dim), rows_idx),
                pl.BlockSpec((sqz, 1, block_m), list_idx),
                pl.BlockSpec((sqz, 1, block_m), probed_idx),
            ],
            out_specs=[
                pl.BlockSpec((sqz, 1, k), query_idx),
                pl.BlockSpec((sqz, 1, k), query_idx),
            ],
            scratch_shapes=[
                pltpu.MemorySpace.VMEM((1, k), jnp.float32),
                pltpu.MemorySpace.VMEM((1, k), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(probe, q[:, None, :], rows, sq.reshape(n_lists, 1, max_len),
      ids.reshape(nq * n_probe, 1, max_len))
    return out_s[:, 0], out_i[:, 0]


def _padded_ids(member_ids: Array, probe: Array, max_len: int) -> Array:
    """The per-query id table, its slots -1 padded to the pack's
    ``max_len``; a 2-D member table has its probed rows gathered first."""
    ids = member_ids
    if ids.ndim == 2:
        ids = probed_ids(ids, probe)
    pad = max_len - ids.shape[2]
    if pad:
        ids = jnp.pad(ids, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
    return ids


def ivf_scan_topk(
    q: Array,
    probe: Array,
    member_ids: Array,
    pack: Dict,
    *,
    k: int,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """Fused stage-0 scan: score every probed list's members, keep the best k.

    Args:
      q:          (Q, D) queries (only ``[:, :pack['dim']]`` is scored).
      probe:      (Q, n_probe) int32 — per-query probed list indices, all in
                  ``[0, n_lists)`` and **distinct within a row** (duplicated
                  probes would double-count their members in the top-k).
      member_ids: (Q, n_probe, max_len) int32 per-query id table
                  (`probed_ids`): global doc ids of each probed list with
                  every unreturnable slot masked to -1 (list padding AND
                  tombstoned rows; the packed member *vectors* are a
                  build-time snapshot and are not consulted for liveness).
                  A (n_lists, max_len) member table, already masked, is
                  also taken and its probed rows gathered here.
      pack:       `pack_ivf_lists` output (member slabs at stage-0 dim).
      k:          neighbours kept (static).
      interpret:  interpret mode for CPU validation.

    Returns:
      ((Q, k) float32 rank-equivalent L2 scores ascending, +inf at empty
      slots; (Q, k) int32 global doc ids, -1 at empty slots).
    """
    if pack["dtype"] == "pq":
        raise ValueError(
            "pq packs are scanned by repro.kernels.pq_scan.pq_ivf_scan_topk "
            "(ADC lookup-table scoring, not a distance matmul)")
    d0, max_len, bm = pack["dim"], pack["max_len"], pack["block_m"]
    nq = q.shape[0]
    if nq == 0:
        return (jnp.zeros((0, k), jnp.float32), jnp.zeros((0, k), jnp.int32))
    qd = q[:, :d0].astype(jnp.float32)
    if pack["dtype"] == "int8":
        # fold the query onto the codes' grid outside the kernel: int32-ish
        # inner products rescaled per-dim by s², db side stays int8
        qd = quant.fold_int8_query(qd, pack["scale"])
    ids = _padded_ids(member_ids, probe, max_len)
    return _ivf_scan_call(
        qd, probe.astype(jnp.int32), pack["rows"], pack["sq"], ids,
        k=k, dim=d0, max_len=max_len, block_m=bm, interpret=interpret,
    )


def stage0_bytes_model(
    *,
    n_lists: int,
    max_len: int,
    n_probe: int,
    d0: int,
    k: int,
    member_bytes: int = 4,
    row_bytes: Optional[float] = None,
    lut_bytes: float = 0.0,
    norms: bool = True,
) -> Dict[str, float]:
    """Modeled per-query stage-0 HBM bytes: fused kernel vs the XLA lowering.

    Both paths share the probe matmul (centroid read, amortized across the
    batch) so it is excluded; the model counts the candidate-dependent terms
    with C = n_probe · max_len:

      XLA   : write + re-read the (C,) id table (top_k gather feeds from it),
              read C member rows (4 B/dim f32), write + re-read the gathered
              (C, d0) tensor (XLA materializes it for the einsum), and
              write + re-read the (C,) f32 score row for top_k.
      fused : stream C member rows once (``member_bytes``/dim, or
              ``row_bytes`` per row when the slab width is decoupled from
              d0 — PQ codes are M bytes/row regardless of d0), plus the
              (C,) id table, the norm side table (``norms=False`` for ADC
              scoring, which needs none), the per-query lookup table
              (``lut_bytes``, PQ only), and the (k,) result.

    The fused path models strictly fewer bytes for every d0 ≥ 1 — the
    acceptance check `benchmarks/backend_comparison.py --ivf-kernel` records.
    """
    c = float(n_probe * max_len)
    xla = (
        2 * 4 * c            # candidate-id table: write + read back
        + 4 * c * d0         # gather reads member rows (f32)
        + 2 * 4 * c * d0     # materialized (C, d0) gather: write + re-read
        + 2 * 4 * c          # (C,) score row: write + read for top_k
    )
    per_row = member_bytes * d0 if row_bytes is None else row_bytes
    fused = (
        per_row * c             # one streaming read of member slabs
        + 4 * c                 # masked id table
        + (4 * c if norms else 0.0)   # packed norms (ADC needs none)
        + lut_bytes             # per-query LUT read (stays VMEM-resident)
        + 8 * k                 # (k,) scores + ids out
    )
    return {"xla_bytes": xla, "fused_bytes": fused,
            "ratio": fused / xla if xla else 0.0}
