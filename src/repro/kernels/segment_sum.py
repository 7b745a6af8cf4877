"""Sorted segment-sum Pallas TPU kernel — the GNN message-passing scatter.

``jax.ops.segment_sum`` lowers to HLO scatter-add: on TPU that serializes
per-row updates through HBM.  With edges *sorted by receiver* the reduction
becomes block-local: the edges of node block [n0, n0+bn) occupy one
contiguous range [indptr[n0], indptr[n0+bn]) of the sorted message array, so
the kernel can stream that range through VMEM and reduce each chunk with a
single MXU matmul:

    out_block += onehot(seg_chunk - n0)ᵀ @ msg_chunk     # (bn,ec)x(ec,D)

Layout (grid = (N/bn,), indptr scalar-prefetched):

    data    : (E, D) ANY  — messages sorted by segment id (HBM-resident)
    seg     : (E, 1) ANY  — sorted segment ids
    indptr  : (N+1,) SMEM — CSR row pointers (scalar prefetch)
    out     : (bn, D) VMEM
    scratch : msg chunk (ec, D) + seg chunk (ec, 1), double-buffered

Padded edges carry segment id >= N and sit at the tail of the sorted order,
beyond indptr[N] — never touched.  The `ops.segment_sum_op` wrapper sorts
unsorted inputs and builds indptr; `ref.segment_sum_ref` is the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


Array = jax.Array


def _kernel(indptr_ref, data_ref, seg_ref, out_ref, buf_d, buf_s, sem,
            *, bn: int, ec: int, d: int):
    g = pl.program_id(0)
    n0 = g * bn
    e_start = indptr_ref[n0]
    e_end = indptr_ref[n0 + bn]
    n_chunks = pl.cdiv(e_end - e_start, ec)

    def copies(chunk, slot):
        e = e_start + chunk * ec
        cp_d = pltpu.make_async_copy(
            data_ref.at[pl.ds(e, ec), :], buf_d.at[slot], sem.at[slot, 0])
        cp_s = pltpu.make_async_copy(
            seg_ref.at[pl.ds(e, ec), :], buf_s.at[slot], sem.at[slot, 1])
        return cp_d, cp_s

    @pl.when(n_chunks > 0)
    def _run():
        for c in copies(0, 0):
            c.start()

        def body(chunk, acc):
            slot = jax.lax.rem(chunk, 2)
            nxt = jax.lax.rem(chunk + 1, 2)

            @pl.when(chunk + 1 < n_chunks)
            def _prefetch():
                for c in copies(chunk + 1, nxt):
                    c.start()

            for c in copies(chunk, slot):
                c.wait()
            msg = buf_d[slot]                              # (ec, D)
            seg = buf_s[slot][:, 0]                        # (ec,)
            # mask rows past this block's edge range (tail chunk overlap)
            e = e_start + chunk * ec
            valid = (jax.lax.broadcasted_iota(jnp.int32, (ec,), 0) + e) < e_end
            local = seg - n0
            onehot = (
                (jax.lax.broadcasted_iota(jnp.int32, (bn, ec), 0)
                 == local[None, :])
                & valid[None, :]
            ).astype(jnp.float32)
            acc = acc + jax.lax.dot_general(
                onehot, msg.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (bn, D)
            return acc

        acc = jax.lax.fori_loop(
            0, n_chunks, body, jnp.zeros((bn, d), jnp.float32))
        out_ref[...] = acc

    @pl.when(n_chunks <= 0)
    def _zero():
        out_ref[...] = jnp.zeros((bn, d), jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_n", "edge_chunk",
                              "interpret"))
def sorted_segment_sum(
    data: Array,
    seg_ids: Array,
    indptr: Array,
    *,
    num_segments: int,
    block_n: int = 128,
    edge_chunk: int = 256,
    interpret: bool = False,
) -> Array:
    """Segment-sum of ``data`` rows, pre-sorted by ``seg_ids``.

    Args:
      data:     (E, D) messages sorted ascending by segment id.  E must allow
                reading ``edge_chunk`` rows past any block boundary (the ops
                wrapper pads the tail; reads are masked).
      seg_ids:  (E,) int32 sorted segment ids (>= num_segments = padding).
      indptr:   (num_segments + 1,) int32 CSR pointers into the sorted order.
      num_segments: output rows (padded to block_n by the wrapper).

    Returns:
      (num_segments, D) float32 sums.
    """
    e, d = data.shape
    assert num_segments % block_n == 0, (num_segments, block_n)
    kernel = functools.partial(_kernel, bn=block_n, ec=edge_chunk, d=d)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_segments // block_n,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            ],
            out_specs=pl.BlockSpec((block_n, d), lambda g, ip: (g, 0)),
            scratch_shapes=[
                pltpu.MemorySpace.VMEM((2, edge_chunk, d), data.dtype),
                pltpu.MemorySpace.VMEM((2, edge_chunk, 1), jnp.int32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        interpret=interpret,
    )(indptr, data, seg_ids[:, None].astype(jnp.int32))
    return out
