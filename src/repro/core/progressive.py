"""Progressive Retrieval — the paper's contribution (§III.D), TPU-native.

Multi-stage search: stage 0 scans the *entire* database at a low truncated
dimensionality keeping K candidates per query; each subsequent stage doubles
the dimensionality, halves K, and rescores only the surviving candidates; the
final stage runs exact 1-NN at the target dimensionality on the remaining
pool.  Early stages are cheap (low dim) but touch everything; late stages are
expensive per row but touch almost nothing — total work collapses from
O(N·D_max) to O(N·D_start + Σ K_s·D_s).

Two variants are provided:

* ``progressive_search`` — **per-query candidate sets, fully static shapes.**
  Every stage has a compile-time-known pool size, so the whole pipeline jits
  into one XLA program and shards under pjit.  This is the TPU adaptation of
  the paper's algorithm (see DESIGN.md §Hardware-adaptation): the paper's
  reference implementation pools candidates across the query batch into one
  deduplicated set, which is a dynamic-shape construct that XLA cannot
  express; per-query sets keep *at least* the paper's per-query candidates,
  so stage-s recall is >= the pooled variant restricted to each query's own
  survivors.

* ``progressive_search_pooled`` — **paper-faithful union pool.**  Candidates
  from all queries are merged into one pool (deduplicated with a static
  bound of Q*K via ``jnp.unique(size=...)``), and every query rescores the
  whole surviving pool each stage, exactly as the reference implementation
  does.  Used by the fidelity benchmarks to validate the per-query variant.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import truncated as T
from repro.core.schedule import ProgressiveSchedule

Array = jax.Array


def _prefix_sq(index: Optional[Dict[str, Array]], dims: Optional[tuple], dim: int):
    """Static lookup of the precomputed prefix-norm column, if available."""
    if index is None or dims is None:
        return None
    dims = tuple(int(x) for x in dims)
    if int(dim) not in dims:
        return None
    return index["sq_prefix"][:, dims.index(int(dim))]


def rescore_ladder(
    q: Array,
    db: Array,
    cand: Array,
    stages,
    *,
    sq_prefix: Optional[Array] = None,
    index_dims: Optional[tuple] = None,
    valid: Optional[Array] = None,
    metric: str = "l2",
    scores: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Chain ``rescore_candidates`` over ``stages`` — the refinement ladder
    every search path shares once it has a candidate table (flat after its
    stage-0 scan, IVF after probing, quantized after the int8 scan).

    ``scores`` is returned unchanged when ``stages`` is empty (degenerate
    single-stage schedules).  The ladder runs under the named scope
    ``rescore``; stage-0 work never calls it, so a device trace counts
    each operation under one of ``stage0`` and ``rescore``.
    """
    index = {"sq_prefix": sq_prefix} if sq_prefix is not None else None
    with jax.named_scope("rescore"):
        for stage in stages:
            scores, cand = T.rescore_candidates(
                q, db, cand,
                dim=stage.dim, k=stage.k,
                db_sq_at_dim=_prefix_sq(index, index_dims, stage.dim),
                valid=valid,
                metric=metric,
            )
    return scores, cand


@functools.partial(
    jax.jit,
    static_argnames=("sched", "index_dims", "block_n", "metric"),
)
def progressive_search(
    q: Array,
    db: Array,
    sched: ProgressiveSchedule,
    *,
    sq_prefix: Optional[Array] = None,
    index_dims: Optional[tuple] = None,
    valid: Optional[Array] = None,
    block_n: int = 65536,
    metric: str = "l2",
) -> Tuple[Array, Array]:
    """Per-query progressive search (static shapes; jit/pjit-native).

    Args:
      q:          (Q, D) queries.
      db:         (N, D) documents.
      sched:      static ProgressiveSchedule (hashable; marked static).
      sq_prefix:  optional (N, len(index_dims)) prefix squared norms
                  (``index['sq_prefix']`` from `repro.core.index.build_index`).
      index_dims: static tuple of dims matching sq_prefix's columns.
      valid:      optional (N,) bool row-validity mask (mutable-corpus
                  serving: deleted / unpopulated rows are unreturnable).
      block_n:    document tile for the stage-0 full scan.
      metric:     'l2' or 'cosine'.

    The program carries two named scopes, so a device trace splits its
    time: ``stage0`` (column slice, padding, blocked scan, top-k) and
    ``rescore`` (the ladder).

    Returns:
      (scores, indices): ((Q, final_k) float32, (Q, final_k) int32).
    """
    index = {"sq_prefix": sq_prefix} if sq_prefix is not None else None

    s0 = sched.stages[0]
    with jax.named_scope("stage0"):
        scores, cand = T.truncated_search(
            q, db,
            dim=s0.dim, k=s0.k,
            db_sq_at_dim=_prefix_sq(index, index_dims, s0.dim),
            valid=valid,
            block_n=block_n, metric=metric,
        )
    return rescore_ladder(
        q, db, cand, sched.stages[1:],
        sq_prefix=sq_prefix, index_dims=index_dims,
        valid=valid, metric=metric, scores=scores,
    )


@functools.partial(
    jax.jit,
    static_argnames=("sched", "index_dims", "block_n", "metric"),
)
def progressive_search_pooled(
    q: Array,
    db: Array,
    sched: ProgressiveSchedule,
    *,
    sq_prefix: Optional[Array] = None,
    index_dims: Optional[tuple] = None,
    valid: Optional[Array] = None,
    block_n: int = 65536,
    metric: str = "l2",
) -> Tuple[Array, Array]:
    """Paper-faithful pooled progressive search.

    After stage 0, candidates of *all* queries are merged into one
    deduplicated pool ("collected and saved in the same candidate pool, so the
    duplicate neighbors will be removed", §III.D); each later stage rescores
    every query against the whole surviving pool and the per-query top-k
    survivors are re-pooled.  Pool sizes are bounded statically by Q*K_s
    (padded with -1), which keeps shapes compile-time constant.

    Returns:
      (scores, indices): ((Q, final_k) float32, (Q, final_k) int32).
    """
    index = {"sq_prefix": sq_prefix} if sq_prefix is not None else None
    nq = q.shape[0]

    s0 = sched.stages[0]
    _, cand = T.truncated_search(
        q, db,
        dim=s0.dim, k=s0.k,
        db_sq_at_dim=_prefix_sq(index, index_dims, s0.dim),
        valid=valid,
        block_n=block_n, metric=metric,
    )

    def pool_of(per_query_cand: Array, bound: int) -> Array:
        """Dedup a (Q, K) candidate table into a (bound,) padded pool."""
        flat = per_query_cand.reshape(-1)
        pool = jnp.unique(flat, size=bound, fill_value=-1)
        return pool

    scores = None
    for stage in sched.stages[1:]:
        bound = min(nq * stage.pool, db.shape[0])
        pool = pool_of(cand, bound)                       # (bound,)
        # Every query scores the whole pool (the paper's "surviving rows").
        pool_tbl = jnp.broadcast_to(pool[None, :], (nq, bound))
        scores, cand = T.rescore_candidates(
            q, db, pool_tbl,
            dim=stage.dim, k=stage.k,
            db_sq_at_dim=_prefix_sq(index, index_dims, stage.dim),
            valid=valid,
            metric=metric,
        )
    if scores is None:  # degenerate single-stage schedule
        scores, cand = T.rescore_candidates(
            q, db, cand, dim=sched.d_max, k=sched.final_k,
            db_sq_at_dim=_prefix_sq(index, index_dims, sched.d_max),
            valid=valid,
            metric=metric,
        )
    return scores, cand
