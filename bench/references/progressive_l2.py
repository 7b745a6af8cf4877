"""Plain reference for progressive search under squared L2.

Written apart from the program under test: plain ``jax.numpy``, no kernels,
no batching, no index, and nothing imported from ``repro``.  It follows the
paper's method (arXiv:2602.07297 §III.D): stage 0 scans every row at the
first ``d_start`` dimensions and keeps ``k0`` candidates per query; each
later stage doubles the dimension, halves the candidate count (never below
``final_k``) and rescores only the survivors; the last stage runs at the
full dimension.  It also gives the exact full-dimension top-k, against which
recall is measured.

The reference computes every product at ``Precision.HIGHEST`` in float32,
at or above the precision each configuration states.  Two switches make a
control of it, the reference one precision below what a configuration
states: ``low=True`` computes every array and every result in bfloat16,
and ``stage0_bits`` rounds the stage-0 prefixes of rows and queries to a
symmetric per-dimension integer grid of that many bits, which stage 0 then
scores exactly, whatever ``low`` says.  Scores are rank-equivalent,
``||x||^2 - 2 q.x``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def schedule(d_start: int, d_max: int, k0: int, final_k: int):
    """((dim, k), ...) of the paper's schedule: dim doubles, k halves."""
    stages = [(d_start, k0)]
    dim, k = d_start, k0
    if d_max > d_start:
        while dim * 2 < d_max:
            dim *= 2
            k = max(k // 2, 1, final_k)
            stages.append((dim, k))
        stages.append((d_max, min(final_k, k)))
    return tuple(stages)


def _dtype(low: bool):
    return jnp.bfloat16 if low else jnp.float32


def _matmul(a, b, low: bool):
    if low:
        return jnp.matmul(a, b)              # bfloat16 in, bfloat16 out
    return jnp.matmul(a, b, precision=HIGHEST)


def _einsum(spec, a, b, low: bool):
    if low:
        return jnp.einsum(spec, a, b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _grid(x, scale, bits: int):
    qmax = 2 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


@functools.partial(jax.jit, static_argnames=("stages", "k_exact", "low",
                                             "bits"))
def _search_chunk(q, db, db0, sq0, sq_full, scale0, *, stages, k_exact, low,
                  bits):
    d0, k0 = stages[0]
    if bits is None:
        q = q.astype(_dtype(low))
        s0 = sq0[None, :] - 2 * _matmul(q[:, :d0], db0.T, low)
    else:
        s0 = sq0[None, :] - 2 * _matmul(_grid(q[:, :d0], scale0, bits),
                                        db0.T, False)
        q = q.astype(_dtype(low))
    cand = jax.lax.top_k(-s0, k0)[1]
    scores = None
    for d, k in stages[1:]:
        x = db[cand, :d]                                   # (Q, C, d)
        s = jnp.sum(x * x, axis=-1) - 2 * _einsum(
            "qd,qcd->qc", q[:, :d], x, low)
        neg, pos = jax.lax.top_k(-s, k)
        cand = jnp.take_along_axis(cand, pos, axis=1)
        scores = -neg
    exact = jax.lax.top_k(
        -(sq_full[None, :] - 2 * _matmul(q, db.T, low)), k_exact)[1]
    return exact, cand, scores


@functools.partial(jax.jit, static_argnames=("low",))
def _scores_of(q, db, ids, *, low):
    q = q.astype(_dtype(low))
    x = db[jnp.maximum(ids, 0)]                            # (R, k, D)
    s = jnp.sum(x * x, axis=-1) - 2 * _einsum("rd,rkd->rk", q, x, low)
    return jnp.where(ids >= 0, s, jnp.nan)


class Reference:
    """The reference over one corpus held on the device."""

    def __init__(self, db: jax.Array, stages, *, k_exact: int,
                 low: bool = False, stage0_bits=None, chunk: int = 128):
        self.low = bool(low)
        self.bits = None if stage0_bits is None else int(stage0_bits)
        self.db = db.astype(_dtype(self.low))
        self.stages = tuple(stages)
        self.k_exact = int(k_exact)
        self.chunk = int(chunk)
        d0 = self.stages[0][0]
        self.db0 = self.db[:, :d0]
        self.scale0 = jnp.ones((d0,), jnp.float32)
        if self.bits is not None:
            # the grid is fitted to the float32 rows and scored in float32
            qmax = 2 ** (self.bits - 1) - 1
            db0 = db[:, :d0].astype(jnp.float32)
            self.scale0 = jnp.max(jnp.abs(db0), axis=0) / qmax
            self.db0 = _grid(db0, self.scale0, self.bits)
        self.sq0 = jnp.sum(self.db0 * self.db0, axis=-1)
        self.sq_full = jnp.sum(self.db * self.db, axis=-1)

    def _chunks(self, a: np.ndarray):
        n = a.shape[0]
        pad = -n % self.chunk
        a = np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
        for lo in range(0, n, self.chunk):
            yield lo, min(self.chunk, n - lo), a[lo:lo + self.chunk]

    def search(self, queries: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(exact top-k ids, progressive ids, progressive scores)."""
        ex, ids, sc = [], [], []
        for _, n, qc in self._chunks(np.asarray(queries, np.float32)):
            e, i, s = _search_chunk(
                jnp.asarray(qc), self.db, self.db0, self.sq0, self.sq_full,
                self.scale0, stages=self.stages, k_exact=self.k_exact,
                low=self.low, bits=self.bits)
            ex.append(np.asarray(e)[:n])
            ids.append(np.asarray(i)[:n])
            sc.append(np.asarray(s, np.float32)[:n])
        return np.concatenate(ex), np.concatenate(ids), np.concatenate(sc)

    def scores_of(self, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Full-dimension scores of given ids (NaN where an id is -1)."""
        out = []
        ids = np.asarray(ids, np.int32)
        for lo, n, qc in self._chunks(np.asarray(queries, np.float32)):
            ic = np.full((self.chunk, ids.shape[1]), -1, np.int32)
            ic[:n] = ids[lo:lo + n]
            out.append(np.asarray(_scores_of(
                jnp.asarray(qc), self.db, jnp.asarray(ic), low=self.low),
                np.float32)[:n])
        return np.concatenate(out)
