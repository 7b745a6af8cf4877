"""Seeded corpus and query pool, generated on the device block by block.

The corpus is the topically clustered mixture that the repository's
``make_clustered_corpus`` draws on the host (documents are gaussians around
``n_clusters`` centres over a decaying per-dimension spectrum), rewritten
here so that the benchmark owns it: every block of ``block_rows`` rows is a
pure function of (seed, block index), so the set-up loads the corpus one
block at a time and the reference regenerates it after the window without a
second resident copy.

The query pool mixes noisy copies of stored rows with fresh draws from the
same mixture, in the share the traffic file gives.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)


def spectrum(dim: int, alpha: float) -> np.ndarray:
    """Per-dimension scale ``(1+j)^-alpha``, normalised to norm sqrt(dim)."""
    s = (1.0 + np.arange(dim)) ** (-alpha)
    return (s / np.linalg.norm(s) * np.sqrt(dim)).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("rows",))
def _mixture_rows(k_topic, k_rows, b, centers, scales, std, *, rows):
    topic = jax.random.randint(jax.random.fold_in(k_topic, b), (rows,), 0,
                               centers.shape[0])
    noise = jax.random.normal(jax.random.fold_in(k_rows, b),
                              (rows, centers.shape[1]), jnp.float32)
    return centers[topic] + std * scales * noise


@functools.partial(jax.jit, static_argnames=("n",))
def _centers(key, scales, spread, *, n):
    return spread * scales * jax.random.normal(key, (n, scales.shape[0]),
                                               jnp.float32)


@jax.jit
def _take_block(picked, rows, src, lo):
    inside = (src >= lo) & (src < lo + rows.shape[0])
    got = rows[jnp.clip(src - lo, 0, rows.shape[0] - 1)]
    return jnp.where(inside[:, None], got, picked)


@jax.jit
def _noisy(key, rows, scales, sigma):
    return rows + sigma * scales * jax.random.normal(key, rows.shape,
                                                     jnp.float32)


class Corpus:
    """The seeded corpus of one configuration (rows live on the device)."""

    def __init__(self, seed: int, n_docs: int, dim: int, params: Dict):
        self.seed = int(seed)
        self.n_docs, self.dim = int(n_docs), int(dim)
        self.block_rows = min(int(params["block_rows"]), self.n_docs)
        if self.n_docs % self.block_rows:
            raise ValueError(f"n_docs {n_docs} is not a multiple of "
                             f"block_rows {self.block_rows}")
        self.n_blocks = self.n_docs // self.block_rows
        self.p = dict(params)
        (self._k_cent, self._k_topic, self._k_rows, self._k_src,
         self._k_noise, self._k_fresh, self._k_order) = jax.random.split(
            base_key(self.seed), 7)
        self.scales = jnp.asarray(spectrum(self.dim, float(params["alpha"])))
        self.centers = _centers(self._k_cent, self.scales,
                                float(params["cluster_spread"]),
                                n=int(params["n_clusters"]))

    def block(self, b: int) -> jax.Array:
        """Rows ``[b * block_rows, (b + 1) * block_rows)`` of the corpus."""
        return _mixture_rows(self._k_topic, self._k_rows, b, self.centers,
                             self.scales, float(self.p["cluster_std"]),
                             rows=self.block_rows)

    def rows(self) -> jax.Array:
        """The whole corpus, regenerated block by block."""
        return jnp.concatenate([self.block(b) for b in range(self.n_blocks)])

    def pool(self, n_pool: int, copy_share: float):
        """(queries (n_pool, dim) f32 host array, source row of each copy
        (-1 for a fresh draw), seeded cycling order of the pool)."""
        n_copy = int(round(n_pool * copy_share))
        src = jnp.sort(jax.random.choice(self._k_src, self.n_docs, (n_copy,),
                                         replace=False))
        picked = jnp.zeros((n_copy, self.dim), jnp.float32)
        for b in range(self.n_blocks):
            # one fixed-shape gather per block: the sources' count in a block
            # varies with the seed, and a shape that varies would compile anew
            picked = _take_block(picked, self.block(b), src,
                                 b * self.block_rows)
        parts = []
        if n_copy:
            parts.append(_noisy(self._k_noise, picked, self.scales,
                                float(self.p["sigma"])))
        if n_pool > n_copy:
            k_topic, k_rows = jax.random.split(self._k_fresh)
            parts.append(_mixture_rows(
                k_topic, k_rows, 0, self.centers, self.scales,
                float(self.p["cluster_std"]), rows=n_pool - n_copy))
        queries = np.asarray(jnp.concatenate(parts), np.float32)
        sources = np.concatenate([np.asarray(src),
                                  np.full(n_pool - n_copy, -1)])
        order = np.asarray(jax.random.permutation(self._k_order, n_pool))
        return queries, sources.astype(np.int64), order.astype(np.int64)
