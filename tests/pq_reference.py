"""Plain reference for progressive search with a PQ stage 0.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, with no kernel, batching or
cache, and nothing imported from ``repro``.  It follows the method of
``repro.core.pq``:

1. ADC tables: for each query and subspace ``m`` the distance terms
   ``||c||^2 - 2 q_m . c`` to every centroid ``c`` of that subspace's
   codebook (the per-query ``||q||^2`` is dropped: scores are
   rank-equivalent).
2. Stage 0: a coded row's score is the sum of its ``M`` table entries; rows
   that are tombstoned or have no codes score +inf; keep the best
   ``oversample * k0``.
3. The tail rows (appended past the coded prefix, so without codes) join
   every query's candidates.
4. The exact ladder: each later stage of the schedule rescores the
   candidates at its dimension, ``||x||^2 - 2 q . x``, and keeps its ``k``.

The codebooks and the codes are data: the reference scores what it is
given and trains nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def adc_tables(q0, codebooks):
    """(Q, M, C) tables of the queries' ``d0``-wide prefixes ``q0``."""
    m, c, dsub = codebooks.shape
    qs = q0.reshape(q0.shape[0], m, dsub)
    cent_sq = jnp.sum(codebooks * codebooks, axis=-1)            # (M, C)
    return cent_sq[None] - 2.0 * jnp.einsum("qmd,mcd->qmc", qs, codebooks)


def adc_scores(tables, codes):
    """(Q, N): the sum of each row's M table entries."""
    m = codes.shape[1]
    # (Q, N, M): row n's entry of each subspace's table
    picked = tables[:, jnp.arange(m)[None, :], codes.astype(jnp.int32)]
    return jnp.sum(picked, axis=-1)


def search(queries, db, codes, codebooks, stages, *, valid, coded, tail,
           oversample):
    """(scores, ids) of the final stage for each query.

    ``db``: (N, D) float32 rows; ``codes``: (N0, M) uint8 codes, of which
    the first ``coded`` are those of rows ``[0, coded)``;
    ``valid``: (N,) bool, False for tombstones; ``tail``: ids of the rows
    past the coded prefix that join every query's candidates;
    ``stages``: ((dim, k), ...) of the schedule."""
    with jax.default_matmul_precision("highest"):
        q = jnp.asarray(queries, jnp.float32)
        db = jnp.asarray(db, jnp.float32)
        valid = jnp.asarray(valid, bool)
        codes = jnp.asarray(codes)
        codebooks = jnp.asarray(codebooks, jnp.float32)
        d0, k0 = stages[0]
        s0 = adc_scores(adc_tables(q[:, :d0], codebooks), codes)
        live = jnp.zeros(codes.shape[0], bool).at[:coded].set(valid[:coded])
        s0 = jnp.where(live[None, :], s0, jnp.inf)
        cand = jax.lax.top_k(-s0, oversample * k0)[1]
        tail = jnp.asarray(np.asarray(tail, np.int32))
        cand = jnp.concatenate(
            [cand, jnp.broadcast_to(tail[None, :], (q.shape[0], tail.size))],
            axis=1)
        scores = None
        for d, k in stages[1:]:
            x = db[cand, :d]                                     # (Q, C, d)
            s = jnp.sum(x * x, axis=-1) - 2.0 * jnp.einsum(
                "qd,qcd->qc", q[:, :d], x)
            s = jnp.where(valid[cand], s, jnp.inf)
            neg, pos = jax.lax.top_k(-s, k)
            cand = jnp.take_along_axis(cand, pos, axis=1)
            scores = -neg
        return np.asarray(scores), np.asarray(cand)
