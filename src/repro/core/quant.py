"""Quantized staged index — precision-progressive search (beyond paper).

The paper's insight is that early search stages need only a *cheap sketch*
of each vector (few leading dimensions).  Precision is the same axis:
stage 0 tolerates int8; only the final exact stage needs full precision.
Composing both, the stage-0 scan reads

    N x Ds x 1 byte      (int8 staged block)

versus ``N x D x 4`` for the naive f32 row-major scan — 16-56x less HBM
traffic at the paper's dimensionalities (D/Ds in [4, 28], x4 bytes).
Scores accumulate in int32 on the MXU (int8 inputs), rank-equivalent to the
dequantized distances up to per-dimension scale rounding; the progressive
rescore at full precision absorbs any stage-0 ranking noise exactly the way
it absorbs truncation noise.

    idx = build_quantized_index(db, sched)
    scores, ids = quantized_progressive_search(q, idx, sched)
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import truncated as T
from repro.core.schedule import ProgressiveSchedule

Array = jax.Array


# -- shared int8 grid helpers -------------------------------------------------
# The one home for per-dimension symmetric int8 bookkeeping: the quantized
# backend, the fused IVF kernel's member-slab packing, and incremental
# append encoding all share the same grid (fit scale -> encode -> fold the
# query), so the math cannot drift between the XLA and Pallas paths.

def fit_int8_scale(x: Array, mask: Optional[Array] = None) -> Array:
    """Per-dimension symmetric scale: ``amax/127`` over (masked) rows.

    ``mask`` selects the rows the grid is fit on (live corpus rows — dead /
    padding slots would drag the grid toward zero); codes can still be
    emitted for every row afterwards.
    """
    ax = jnp.abs(x.astype(jnp.float32))
    if mask is not None:
        ax = jnp.where(mask[:, None], ax, 0.0)
    amax = jnp.max(ax, axis=0)
    return jnp.maximum(amax, 1e-12) / 127.0


def int8_encode(x: Array, scale: Array) -> Tuple[Array, Array]:
    """Code rows onto an existing grid.

    Returns (codes (N, D) int8, deq_sq (N,) f32) where ``deq_sq`` holds the
    squared norms of the *dequantized* rows — the norm table every int8
    scoring path pairs with the codes.
    """
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
    deq = codes.astype(jnp.float32) * scale
    return codes, jnp.sum(deq * deq, axis=-1)


def fold_int8_query(q: Array, scale: Array) -> Array:
    """Fold a query onto the codes' grid for rank-equivalent int8 scoring.

    Distances in the *scaled* space (x_d / s_d) are NOT rank-equivalent to
    true distances, so the query is quantized onto the same grid and the
    per-dim ``s_d^2`` rescale is folded into the query side:
    ``ip = (round(clip(q/s)) * s^2) @ codes^T`` keeps the db operand — the
    side that dominates HBM traffic — int8.
    """
    qq = jnp.clip(jnp.round(q.astype(jnp.float32) / scale), -127, 127)
    return (qq * scale * scale).astype(jnp.float32)


def pad_pow2(a: np.ndarray) -> np.ndarray:
    """Pad axis 0 up to a power of two by repeating the last element.

    Scatter updates are idempotent under repeats (same dest, same value),
    and bounding the batch shape to O(log B) distinct sizes keeps jitted
    append-scatters from retracing on every burst size.
    """
    a = np.asarray(a)
    n = a.shape[0]
    target = 1 << (max(n, 1) - 1).bit_length()
    if target == n:
        return a
    reps = np.ones(n, np.int64)
    reps[-1] = target - n + 1
    return np.repeat(a, reps, axis=0)


# incremental-append scatters, shared by the quantized backend's code block
# and the fused kernels' member-slab packs: on accelerators the target
# buffers are DONATED so XLA updates them in place (absorbing a handful of
# rows must not copy an O(corpus) buffer); CPU has no donation and pays the
# copy, which only matters for interpret-mode validation
_scatter_rows_donate = jax.jit(
    lambda buf, dests, rows: buf.at[dests].set(rows), donate_argnums=(0,))
_scatter_rows_copy = jax.jit(
    lambda buf, dests, rows: buf.at[dests].set(rows))
_scatter_rows2_donate = jax.jit(
    lambda a, b, dests, ra, rb: (a.at[dests].set(ra), b.at[dests].set(rb)),
    donate_argnums=(0, 1))
_scatter_rows2_copy = jax.jit(
    lambda a, b, dests, ra, rb: (a.at[dests].set(ra), b.at[dests].set(rb)))


def scatter_rows(buf: Array, dests: Array, rows: Array) -> Array:
    """Scatter ``rows`` into ``buf[dests]``, in place off-CPU (donation)."""
    fn = (_scatter_rows_copy if jax.default_backend() == "cpu"
          else _scatter_rows_donate)
    return fn(buf, dests, rows)


def scatter_rows2(a: Array, b: Array, dests: Array,
                  ra: Array, rb: Array) -> Tuple[Array, Array]:
    """Paired scatter (codes + their norm table) sharing one dest batch."""
    fn = (_scatter_rows2_copy if jax.default_backend() == "cpu"
          else _scatter_rows2_donate)
    return fn(a, b, dests, ra, rb)


def quantize_per_dim(x: Array, valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """Symmetric per-dimension int8 quantization.

    Returns (q (N, D) int8, scale (D,) f32) with x ≈ q * scale.  When a
    ``valid`` row mask is given, the scale is fit on live rows only (dead /
    unpopulated buffer slots would otherwise drag the grid toward zero), but
    codes are still emitted for every row.
    """
    scale = fit_int8_scale(x, valid)
    q, _ = int8_encode(x, scale)
    return q, scale


def build_quantized_index(
    db: Array, sched: ProgressiveSchedule, *, valid: Optional[Array] = None
) -> Dict[str, Array]:
    """Stage-0 int8 block + full-precision corpus + stage-0 squared norms."""
    ds = sched.stages[0].dim
    scale0 = fit_int8_scale(db[:, :ds], valid)
    q0, deq_sq = int8_encode(db[:, :ds], scale0)
    return {
        "db": db,
        "db0_q": q0,                 # (N, Ds) int8
        "scale0": scale0,            # (Ds,) f32
        "sq0": deq_sq,               # (N,) norms of the dequantized block
    }


def _scaled_space_scores(q: Array, idx: Dict[str, Array]) -> Array:
    """Rank-equivalent stage-0 scores computed wholly in scaled int8 space
    (see `fold_int8_query` for why the rescale rides on the query side)."""
    db0_q = idx["db0_q"]
    ds = db0_q.shape[1]
    q_scaled = fold_int8_query(q[:, :ds], idx["scale0"])  # (Q, Ds)
    ip = jax.lax.dot_general(
        q_scaled, db0_q.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return idx["sq0"][None, :] - 2.0 * ip


def quant_rest_stages(sched, *, extra_cand=None, valid=None):
    """Post-stage-0 ladder stages for the quantized / PQ families:
    ``stages[1:]``, except that a single-stage schedule with injected or
    masked candidates still needs one exact pass so those candidates carry
    full-precision scores."""
    rest = sched.stages[1:]
    if not rest and (extra_cand is not None or valid is not None):
        rest = (sched.stages[0],)
    return rest


@functools.partial(jax.jit, static_argnames=("sched", "metric"))
def quantized_progressive_search(
    q: Array, idx: Dict[str, Array], sched: ProgressiveSchedule,
    *, metric: str = "l2",
    db: Optional[Array] = None,
    valid: Optional[Array] = None,
    row_limit: Optional[Array] = None,
    extra_cand: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Progressive search with an int8 stage-0 block.

    Stage 0 ranks with quantized scores; every later stage rescores the
    survivors at full precision, so the final results carry exact distances.

    Mutable-corpus extensions (all optional, used by the engine's
    ``QuantizedProgressiveBackend``):

      db:         rescore buffer when the index's ``db`` snapshot is stale
                  (rows < the snapshot length are append-only identical, so
                  the stage-0 codes stay exact for the rows they cover).
      valid:      (N,) bool row mask over ``db``; invalid rows are scored
                  +inf at stage 0 and at every rescore.
      row_limit:  scalar — rows >= it are excluded from stage-0 ranking
                  (their codes predate them); pair with ``extra_cand`` to
                  keep them reachable.
      extra_cand: (E,) int32 ids injected after stage 0 (-1 padded), rescored
                  at full precision; must be disjoint from stage-0 rows.

    Named scopes: ``stage0`` (the int8 scan and top-k), ``rescore``.
    """
    from repro.core.progressive import rescore_ladder

    s0 = sched.stages[0]
    rescore_db = idx["db"] if db is None else db
    with jax.named_scope("stage0"):
        scores = _scaled_space_scores(q, idx)
        n0 = scores.shape[1]
        keep = jnp.ones((n0,), bool)
        if valid is not None:
            keep = keep & valid[:n0]
        if row_limit is not None:
            keep = keep & (jnp.arange(n0) < row_limit)
        scores = jnp.where(keep[None, :], scores, jnp.inf)
        neg, cand = jax.lax.top_k(-scores, min(s0.k, n0))
        # fully-masked slots must surface the -1 sentinel, not row 0
        cand = jnp.where(jnp.isfinite(-neg), cand.astype(jnp.int32), -1)
        scores = -neg
        cand = T.inject_candidates(cand, extra_cand)
    return rescore_ladder(
        q, rescore_db, cand,
        quant_rest_stages(sched, extra_cand=extra_cand, valid=valid),
        valid=valid, metric=metric, scores=scores,
    )
