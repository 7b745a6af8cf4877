"""EmbeddingBag Pallas TPU kernel — the recsys hot path.

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse; the framework-level
implementation (``repro.models.recsys.embedding_bag``) is ``jnp.take`` +
``segment_sum``.  That lowering materializes the gathered (B, L, D) tensor in
HBM before reducing — for a DLRM batch of 65536 × 26 fields that is the
dominant memory term.  This kernel fuses gather + bag-reduce: table rows are
DMA'd HBM→VMEM per bag and accumulated in registers, so HBM traffic is one
row-read per index plus one (B, D) result write.

Layout (grid = (B // block_b,)):

    indices : (B, L) int32  — scalar-prefetched (DMA addresses)
    table   : (V, D) ANY    — stays in HBM
    out     : (block_b, D) VMEM
    buf     : (2, D) VMEM   — double-buffered row landing slot

Supports 'sum' and 'mean' over fixed-size bags with -1 padding (multi-hot
fields padded to L — the standard TPU-friendly recsys batch layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _kernel(idx_ref, table_ref, out_ref, buf, sem, *, block_b: int, bag: int, mode: str):
    g = pl.program_id(0)

    def row_copy(idx, slot):
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(jnp.maximum(idx, 0), 1), :],
            buf.at[pl.ds(slot, 1), :],
            sem.at[slot],
        )

    def bag_body(b, _):
        row = g * block_b + b
        first = idx_ref[row, 0]
        row_copy(first, 0).start()

        def acc_body(l, carry):
            acc, cnt = carry
            slot = jax.lax.rem(l, 2)
            nxt = jax.lax.rem(l + 1, 2)

            @pl.when(l + 1 < bag)
            def _prefetch():
                row_copy(idx_ref[row, l + 1], nxt).start()

            row_copy(idx_ref[row, l], slot).wait()
            valid = (idx_ref[row, l] >= 0).astype(jnp.float32)
            acc = acc + valid * buf[slot].astype(jnp.float32)
            cnt = cnt + valid
            return acc, cnt

        acc0 = jnp.zeros_like(buf[0], dtype=jnp.float32)
        acc, cnt = jax.lax.fori_loop(0, bag, acc_body, (acc0, 0.0))
        if mode == "mean":
            acc = acc / jnp.maximum(cnt, 1.0)
        out_ref[b, :] = acc
        return ()

    jax.lax.fori_loop(0, block_b, bag_body, ())


@functools.partial(
    jax.jit, static_argnames=("mode", "block_b", "interpret")
)
def embedding_bag(
    table: Array,
    indices: Array,
    *,
    mode: str = "sum",
    block_b: int = 8,
    interpret: bool = False,
) -> Array:
    """Fused gather + per-bag reduce over an HBM-resident embedding table.

    Args:
      table:   (V, D) embedding table.
      indices: (B, L) int32 ids per bag, -1 = padding.
      mode:    'sum' | 'mean'.
      block_b: bags per grid step.
      interpret: interpret mode for CPU validation.

    Returns:
      (B, D) float32 bag embeddings.
    """
    if mode not in ("sum", "mean"):
        raise ValueError(f"kernel supports sum|mean, got {mode}")
    b, bag = indices.shape
    v, d = table.shape
    pb = -b % block_b
    idx_p = jnp.pad(indices, ((0, pb), (0, 0)), constant_values=-1) if pb else indices
    bp = idx_p.shape[0]

    kernel = functools.partial(_kernel, block_b=block_b, bag=bag, mode=mode)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp // block_b,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=pl.BlockSpec((block_b, d), lambda g, idx: (g, 0)),
            scratch_shapes=[
                pltpu.MemorySpace.VMEM((2, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bp, d), jnp.float32),
        interpret=interpret,
    )(idx_p, table)
    return out[:b]
