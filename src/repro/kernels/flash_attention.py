"""FlashAttention-style fused attention Pallas TPU kernel.

The LM architectures in the zoo (prefill at 32k, decode against long caches)
need attention whose peak memory does not include the (S, S) score matrix.
The framework's model code uses a mathematically identical chunked
online-softmax in pure JAX (`repro.layers.attention.chunked_attention`) for
the CPU dry-run lowering; on real TPU this kernel is the drop-in replacement
(same signature, validated against `repro.kernels.ref.flash_attention_ref`).

Tiling (grid = (B·H, Sq/bq, Skv/bk), kv innermost/sequential):

    q_ref  : (1, bq, dh) VMEM     acc    : (bq, dh) f32 scratch
    k_ref  : (1, bk, dh) VMEM     m, l   : (bq, 1)  f32 scratch (running max/sum)
    v_ref  : (1, bk, dh) VMEM     out    : (1, bq, dh)

Causal and sliding-window masks are applied per-tile; tiles that are fully
masked under the causal/window pattern are skipped via ``pl.when`` (block
sparsity — this is what makes the gemma3 5:1 local:global pattern profitable
at long context).  Query positions are aligned to the *end* of kv, so the
same kernel serves prefill (sq == skv) and decode (sq << skv).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i,
    *, scale: float, causal: bool, window: Optional[int],
    bq: int, bk: int, sq: int, skv: int,
):
    iq = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, _NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    offset = skv - sq  # absolute position of q row 0

    def compute():
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                            # (bq, bk)
        mask = k_pos < skv  # exclude kv padding
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_i[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        acc[...] = acc[...] * alpha + pv
        m_i[...] = m_new

    skip = None
    if causal:
        # tile entirely above the causal diagonal (first k of tile beyond the
        # last q position of the tile) contributes nothing
        last_q_pos = (iq + 1) * bq - 1 + offset
        skip = j * bk > last_q_pos
    if window is not None:
        # tile entirely left of the window of the tile's *first* q row
        first_q_pos = iq * bq + offset
        too_old = (j + 1) * bk - 1 <= first_q_pos - window
        skip = too_old if skip is None else (skip | too_old)

    if skip is None:
        compute()
    else:
        pl.when(jnp.logical_not(skip))(compute)

    @pl.when(j == nj - 1)
    def _flush():
        o_ref[0] = (acc[...] / jnp.maximum(l_i[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> Array:
    """Fused attention.  q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh).

    GQA handled by repeating kv heads (view-level repeat; on real TPU prefer
    reshaping q to share kv tiles across the q-head group).

    Returns (B, Hq, Sq, Dh) in q's dtype.
    """
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if scale is None:
        scale = 1.0 / (dh ** 0.5)

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pq, pk = -sq % bq, -skv % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    sqp, skp = q.shape[2], k.shape[2]

    qf = q.reshape(b * hq, sqp, dh)
    kf = k.reshape(b * hq, skp, dh)
    vf = v.reshape(b * hq, skp, dh)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sq=sq, skv=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sqp // bq, skp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sqp, dh), q.dtype),
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((bq, dh), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, 1), jnp.float32),
            pltpu.MemorySpace.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sqp, dh)[:, :, :sq]
