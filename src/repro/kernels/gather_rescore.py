"""Fused candidate gather + high-dim rescore Pallas TPU kernel.

Late progressive-search stages score each query only against *its own*
surviving candidates, at a higher dimensionality.  A naive XLA lowering
materializes the gathered (Q, C, D) tensor in HBM (for the paper's workload:
2470 × 128 × 3584 × 4 B ≈ 4.5 GB written + re-read).  This kernel performs the
gather as row-granular HBM→VMEM DMAs (the database never leaves HBM whole)
and computes the distances in the same pass — the PagedAttention-style
"indirection" kernel regime adapted from KV-block lookup to ANN candidate
lookup (DESIGN.md §Hardware-adaptation).

Layout (grid = (Q,); one query per step):

    cand   : (Q, C) int32   — scalar-prefetched so DMA source addresses are
                              known before the kernel body runs
    q_ref  : (1, D)  VMEM   — the query row
    db_ref : (N, D)  ANY    — stays in HBM; rows DMA'd on demand
    buf    : (2, bc, D) VMEM scratch — double-buffered candidate slab
    out    : (1, C) float32 — rank-equivalent L2 scores

The candidate axis is processed in chunks of ``bc`` rows; chunk j+1's DMAs
are issued before chunk j's compute, overlapping gather latency with the VPU
distance math.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _kernel(cand_ref, q_ref, db_ref, out_ref, buf, sem, *, bc: int, c_total: int):
    i = pl.program_id(0)
    n_chunks = c_total // bc

    def issue(chunk, slot):
        """Start DMAs for all rows of one candidate chunk into buf[slot]."""
        def issue_row(r, _):
            idx = cand_ref[i, chunk * bc + r]
            idx = jnp.maximum(idx, 0)  # padded (-1) rows fetch row 0; masked later
            pltpu.make_async_copy(
                db_ref.at[pl.ds(idx, 1), :],
                buf.at[slot, pl.ds(r, 1), :],
                sem.at[slot],
            ).start()
            return ()

        jax.lax.fori_loop(0, bc, issue_row, ())

    def wait(slot):
        def wait_row(r, _):
            pltpu.make_async_copy(
                db_ref.at[pl.ds(0, 1), :],
                buf.at[slot, pl.ds(0, 1), :],
                sem.at[slot],
            ).wait()
            return ()

        jax.lax.fori_loop(0, bc, wait_row, ())

    issue(0, 0)
    q = q_ref[...]  # (1, D)

    def body(chunk, _):
        slot = jax.lax.rem(chunk, 2)
        nxt = jax.lax.rem(chunk + 1, 2)

        @pl.when(chunk + 1 < n_chunks)
        def _prefetch():
            issue(chunk + 1, nxt)

        wait(slot)
        rows = buf[slot]                                   # (bc, D)
        sq = jnp.sum(rows.astype(jnp.float32) ** 2, axis=-1, keepdims=True).T
        ip = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # (1, bc)
        scores = sq - 2.0 * ip
        out_ref[0, pl.ds(chunk * bc, bc)] = scores[0]
        return ()

    jax.lax.fori_loop(0, n_chunks, body, ())


@functools.partial(
    jax.jit, static_argnames=("block_c", "interpret")
)
def gather_rescore(
    q: Array,
    db: Array,
    cand: Array,
    *,
    block_c: int = 16,
    interpret: bool = False,
) -> Array:
    """Score each query against its candidate rows without materializing the gather.

    Args:
      q:       (Q, D) queries.
      db:      (N, D) database (HBM-resident).
      cand:    (Q, C) int32 candidate indices, -1 = padding.
      block_c: candidate rows DMA'd per chunk (C padded to a multiple).
      interpret: interpret mode for CPU validation.

    Returns:
      (Q, C) float32 rank-equivalent scores (``||x||² − 2 q·x``), +inf at pads.
    """
    nq, d = q.shape
    c = cand.shape[1]
    pc = -c % block_c
    if pc:
        cand_p = jnp.pad(cand, ((0, 0), (0, pc)), constant_values=-1)
    else:
        cand_p = cand
    c_total = cand_p.shape[1]

    kernel = functools.partial(_kernel, bc=block_c, c_total=c_total)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nq,),
            in_specs=[
                pl.BlockSpec((1, d), lambda i, cand: (i, 0)),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            ],
            out_specs=pl.BlockSpec((1, c_total), lambda i, cand: (i, 0)),
            scratch_shapes=[
                pltpu.MemorySpace.VMEM((2, block_c, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((nq, c_total), jnp.float32),
        interpret=interpret,
    )(cand_p, q, db)
    out = jnp.where(cand_p >= 0, out, jnp.inf)
    return out[:, :c]


def gather_rescore_topk(
    q: Array,
    db: Array,
    cand: Array,
    *,
    k: int,
    block_c: int = 16,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """Convenience: fused rescore + top-k (selection outside the kernel)."""
    s = gather_rescore(q, db, cand, block_c=block_c, interpret=interpret)
    neg, pos = jax.lax.top_k(-s, k)
    return -neg, jnp.take_along_axis(cand, pos, axis=1)
