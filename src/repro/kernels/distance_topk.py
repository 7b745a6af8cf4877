"""Fused L2-distance + streaming top-k Pallas TPU kernel.

This is the stage-0 hot loop of progressive retrieval: score every database
row against a query block at a truncated dimensionality and keep the best k
per query.  The fusion is the point — for Q=2470 queries and N=1M docs the
(Q, N) score matrix is ~10 GB; computing it through HBM makes the scan
memory-bound.  The kernel keeps the running top-k in VMEM scratch, so HBM
traffic collapses to *one streaming read of the database* (N·d bytes) plus a
(Q, k) result — which pushes the scan from the memory roofline onto the
compute (MXU) roofline.

Tiling (grid = (Q/bq, N/bn); the document axis is the inner, sequential,
dimension so the top-k carry in scratch is valid — TPU grids execute in
row-major order and revisit scratch in place):

              d (stage dim)                 k
    q_ref  : (bq, d)    VMEM     out_s  : (bq, k)  VMEM
    db_ref : (bn, d)    VMEM     out_i  : (bq, k)  VMEM
    sq_ref : (1, bn)    VMEM     scratch: best_s/best_i (bq, k)

Per tile: ``scores = sq - 2 * q @ db^T`` on the MXU (f32 accumulate), then the
tile's candidates are folded into the carry by `merge_topk` and the carry
is ordered once by `sort_topk` at the last tile.  Both are written with iota
compares, ``jnp.where`` and lane reductions only: Mosaic lowers no
``top_k``, gather or scatter inside a kernel.  The same two functions merge
the running top-k of `repro.kernels.ivf_scan` and `repro.kernels.pq_scan`.

Validated against `repro.kernels.ref.l2_topk_ref` in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def merge_topk(best_s: Array, best_i: Array, s: Array, ids: Array
               ) -> Tuple[Array, Array]:
    """Fold a block of candidates into an unsorted running top-k.

    ``best_s``/``best_i`` (R, k) hold each row's k best (score, id) pairs in
    no particular order, with (+inf, -1) in empty slots; ``s``/``ids``
    (R, width) are the block's candidates.  Each round moves the block's
    smallest remaining score (lowest column on ties) into the slot of the
    row's current worst when it is strictly smaller, so ties keep what the
    carry already holds.  The trip count is the largest per-row number of
    block scores under the row's worst carried score: once the carry is
    full, most blocks cost one compare and no rounds.

    Only iota compares, ``jnp.where`` and lane reductions — no ``top_k``,
    gather or scatter, which Mosaic does not lower.
    """
    r, k = best_s.shape
    width = s.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (r, width), 1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)
    worst0 = jnp.max(best_s, axis=1, keepdims=True)
    n_new = jnp.max(jnp.sum((s < worst0).astype(jnp.int32), axis=1))

    def body(_, carry):
        bs, bi, s = carry
        m = jnp.min(s, axis=1, keepdims=True)                     # (r, 1)
        first = jnp.min(jnp.where(s == m, cols, width), axis=1, keepdims=True)
        hit = cols == first
        m_id = jnp.sum(jnp.where(hit, ids, 0), axis=1, keepdims=True)
        s = jnp.where(hit, jnp.inf, s)
        worst = jnp.max(bs, axis=1, keepdims=True)
        slot = jnp.min(jnp.where(bs == worst, kcols, k), axis=1, keepdims=True)
        put = (kcols == slot) & (m < worst)
        return jnp.where(put, m, bs), jnp.where(put, m_id, bi), s

    best_s, best_i, _ = jax.lax.fori_loop(0, n_new, body, (best_s, best_i, s))
    return best_s, best_i


def sort_topk(best_s: Array, best_i: Array) -> Tuple[Array, Array]:
    """Order a `merge_topk` carry ascending by score, ties by lower id.

    k rounds of (min, first-id) extraction; slots still empty come out as
    (+inf, -1).  Same lowering constraints as `merge_topk`.
    """
    r, k = best_s.shape
    kcols = jax.lax.broadcasted_iota(jnp.int32, (r, k), 1)
    big = jnp.iinfo(jnp.int32).max

    def body(j, carry):
        s, i, out_s, out_i = carry
        m = jnp.min(s, axis=1, keepdims=True)
        is_min = s == m
        m_id = jnp.min(jnp.where(is_min, i, big), axis=1, keepdims=True)
        first = jnp.min(jnp.where(is_min & (i == m_id), kcols, k),
                        axis=1, keepdims=True)
        taken = kcols == first
        at_j = kcols == j
        out_s = jnp.where(at_j, m, out_s)
        out_i = jnp.where(at_j, jnp.where(m < jnp.inf, m_id, -1), out_i)
        return (jnp.where(taken, jnp.inf, s), jnp.where(taken, big, i),
                out_s, out_i)

    _, _, out_s, out_i = jax.lax.fori_loop(
        0, k, body, (best_s, best_i, best_s, best_i))
    return out_s, out_i


def _kernel(
    q_ref, db_ref, sq_ref, out_s_ref, out_i_ref, best_s, best_i,
    *, k: int, bn: int, n_valid: int,
):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        best_s[...] = jnp.full_like(best_s, jnp.inf)
        best_i[...] = jnp.full_like(best_i, -1)

    q = q_ref[...]
    db = db_ref[...]
    sq = sq_ref[...]  # (1, bn)

    ip = jax.lax.dot_general(
        q, db, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    scores = sq - 2.0 * ip                                     # (bq, bn)
    base = j * bn
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + base
    # Mask rows past the true db length (padding tile).
    scores = jnp.where(col < n_valid, scores, jnp.inf)

    new_s, new_i = merge_topk(best_s[...], best_i[...], scores, col)
    best_s[...] = new_s
    best_i[...] = new_i

    @pl.when(j == nj - 1)
    def _flush():
        out_s_ref[...], out_i_ref[...] = sort_topk(best_s[...], best_i[...])


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_q", "block_n", "interpret"),
)
def l2_topk(
    q: Array,
    db: Array,
    *,
    k: int,
    db_sq: Optional[Array] = None,
    block_q: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """Fused distance+top-k scan of ``db`` for each row of ``q``.

    Args:
      q:      (Q, d) queries.
      db:     (N, d) database (same trailing dim; truncate before calling).
      k:      neighbours kept (static; k <= block_n).
      db_sq:  optional (N,) precomputed squared norms.
      block_q/block_n: VMEM tile sizes.  ``d * (block_q + block_n) * 4`` bytes
        plus the (block_q, block_n) score tile must fit VMEM (~16 MB/core).
      interpret: run the kernel in interpret mode (CPU validation).

    Returns:
      ((Q, k) float32 rank-equivalent scores ascending, (Q, k) int32 indices).
    """
    nq, d = q.shape
    n, d2 = db.shape
    assert d == d2, (d, d2)
    if k > block_n:
        raise ValueError(f"k={k} must be <= block_n={block_n}")
    if db_sq is None:
        db_sq = jnp.sum(db.astype(jnp.float32) ** 2, axis=-1)

    # Pad every axis to tile multiples.
    pq = -nq % block_q
    pn = -n % block_n
    if pq:
        q = jnp.pad(q, ((0, pq), (0, 0)))
    if pn:
        db = jnp.pad(db, ((0, pn), (0, 0)))
        db_sq = jnp.pad(db_sq, (0, pn), constant_values=jnp.inf)
    sq2d = db_sq.reshape(1, -1)

    grid = (q.shape[0] // block_q, db.shape[0] // block_n)
    kernel = functools.partial(
        _kernel, k=k, bn=block_n, n_valid=n
    )
    out_s, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q.shape[0], k), jnp.float32),
            jax.ShapeDtypeStruct((q.shape[0], k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((block_q, k), jnp.float32),
            pltpu.MemorySpace.VMEM((block_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, db, sq2d)
    return out_s[:nq], out_i[:nq]
