"""Flat progressive backend — the engine's original search path, extracted.

No build artifact beyond the store's own buffers (the prefix-norm table is
maintained incrementally by ``DocStore.add``), so the state is a bare
snapshot record: builds are free, nothing ever goes stale, and every row is
covered the moment it lands in the buffer.  This is the exactness baseline
the approximate backends are benchmarked against.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.core import progressive_search
from repro.index_backends.base import (
    IndexBackend,
    IndexState,
    StoreStats,
    register_backend,
)

Array = jax.Array


@register_backend
class FlatProgressiveBackend(IndexBackend):
    """Stage-0 full scan at truncated dims + progressive rescore (paper §III.D)."""

    name = "flat"

    def build(
        self,
        db: Array,
        valid: Array,
        *,
        sq_prefix: Optional[Array] = None,
        stats: StoreStats,
    ) -> IndexState:
        return IndexState.from_stats(self.name, stats,
                                     shape_key=(self.name,))

    def search(
        self,
        q: Array,
        state: IndexState,
        db: Array,
        valid: Array,
        *,
        sq_prefix: Optional[Array] = None,
        n_total: int,
        k: int,
        overrides=None,
    ) -> Tuple[Array, Array]:
        # adaptive degradation: swap in the shallower schedule (higher
        # stage-0 truncation error, same final_k → same result width);
        # its stage dims are present in self.dims, so sq-prefix lookups
        # stay precomputed
        sched = self.sched if overrides is None or overrides.sched is None \
            else overrides.sched
        scores, ids = progressive_search(
            q, db, sched,
            sq_prefix=sq_prefix,
            index_dims=self.dims,
            valid=valid,
            block_n=min(self.block_n, db.shape[0]),
            metric=self.metric,
        )
        # scores ascend; the leading k columns are the top results (only a
        # single-stage schedule is wider than the engine's out_k)
        return scores[:, :k], ids[:, :k]
