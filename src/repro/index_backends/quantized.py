"""Quantized-progressive backend: coded stage-0 scan, full-precision rescore.

The stage-0 scan still touches every row, but reads a compressed sketch —
the paper's "cheap sketch" idea applied to precision instead of (and
composed with) dimensionality.  Two codecs share the backend:

* ``codec='int8'`` — per-dimension symmetric int8 codes: 1 byte/dim, 4x
  less stage-0 HBM traffic than f32 (`repro.core.quant`).
* ``codec='pq'``  — product-quantization codes: ``pq_m`` uint8 codes/row
  against per-subspace k-means codebooks, scored by ADC lookup tables
  (`repro.core.pq`) — 4–8x less traffic than int8 again.  With
  ``use_kernel`` the scan runs the fused Pallas LUT kernel
  (`repro.kernels.pq_scan`): the per-query (M, C) table stays VMEM-resident
  while code slabs stream HBM→VMEM once.

**Churn-aware maintenance.**  The code block is a build artifact, but the
grid it is coded on (int8 scale / PQ codebooks) is *frozen* between
rebuilds: rows appended later are encoded against the frozen grid at
engine safe points (``absorb_appends``) and scattered into the code
block in place, so append-heavy workloads stop forcing early rebuilds —
only rows past the block's capacity ride the tail window.  Codebooks and
scales are refit at the next rebuild safe point, which is also when
distribution drift from churn is absorbed; drift is a quality (not
correctness) concern — the rescore ladder runs at full precision either
way — and is what ``needs_rebuild``'s churn budget bounds.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.quant import (
    build_quantized_index,
    int8_encode,
    pad_pow2,
    quantized_progressive_search,
    scatter_rows,
    scatter_rows2,
)
from repro.index_backends.base import (
    ChurnRebuildBackend,
    IndexState,
    StoreStats,
    register_backend,
)

Array = jax.Array


@register_backend
class QuantizedProgressiveBackend(ChurnRebuildBackend):
    """Coded stage-0 block scan + exact progressive rescore."""

    name = "quantized"

    def __init__(
        self,
        sched,
        *,
        metric: str = "l2",
        block_n: int = 65536,
        rebuild_frac: float = 0.25,
        min_rebuild_rows: int = 64,
        tail_window: int = 512,
        codec: str = "int8",
        pq_m: Optional[int] = None,
        pq_codes: int = 256,
        pq_iters: int = 10,
        pq_train_rows: int = 65536,
        pq_oversample: int = 4,
        encode_appends: bool = True,
        use_kernel="auto",
        kernel_block_m: int = 128,
        seed: int = 0,
    ):
        """Args beyond the shared churn config:

        codec:          'int8' (per-dim symmetric codes) | 'pq' (product
                        quantization: pq_m uint8 codes/row + ADC tables).
        pq_m:           'pq' only: subspaces per stage-0 row (None: aim
                        4-dim subspaces — `repro.core.pq.auto_pq_m`); must
                        divide the stage-0 dim.
        pq_codes:       'pq' only: centroids per subspace (<= 256).
        pq_iters:       'pq' only: k-means iterations per subspace.
        pq_train_rows:  'pq' only: codebooks train on at most this many
                        sampled live rows.
        pq_oversample:  'pq' only: stage-0 survivor pool widens to
                        ``pq_oversample × k0`` — ADC ranking noise is
                        absorbed by the full-precision rescore, which cuts
                        the pool back (the classic IVF-PQ re-rank trick).
        encode_appends: encode appended rows against the frozen grid at
                        engine safe points (in-place code-block scatter)
                        instead of riding the tail window; False restores
                        pure tail-window behavior.
        use_kernel:     'pq' only: 'auto' | True | False — stage-0 via the
                        fused Pallas ADC LUT kernel ('auto': TPU only;
                        True forces it, interpret mode off-TPU; False: the
                        XLA ADC reference).  int8 stage 0 is a plain
                        matmul — XLA already lowers it well.
        kernel_block_m: code-slab rows per kernel step.
        """
        super().__init__(
            sched, metric=metric, block_n=block_n,
            rebuild_frac=rebuild_frac, min_rebuild_rows=min_rebuild_rows,
            tail_window=tail_window,
        )
        if metric != "l2":
            raise ValueError(
                "QuantizedProgressiveBackend supports metric='l2' only "
                "(coded stage-0 scores are rank-equivalent L2 distances)"
            )
        if codec not in ("int8", "pq"):
            raise ValueError(f"codec must be int8|pq, got {codec!r}")
        if use_kernel not in ("auto", True, False):
            raise ValueError(
                f"use_kernel must be 'auto'|True|False, got {use_kernel!r}")
        if use_kernel is True and codec != "pq":
            raise ValueError(
                "use_kernel applies to codec='pq' (the fused ADC LUT "
                "kernel); the int8 stage 0 is already a plain XLA matmul")
        self.codec = codec
        self.pq_codes = int(pq_codes)
        self.pq_iters = int(pq_iters)
        self.pq_train_rows = int(pq_train_rows)
        self.pq_oversample = max(1, int(pq_oversample))
        self.encode_appends = bool(encode_appends)
        self.use_kernel = use_kernel
        self.kernel_block_m = int(kernel_block_m)
        self.seed = int(seed)
        s0_dim = sched.stages[0].dim
        if codec == "pq":
            from repro.core.pq import auto_pq_m
            self.pq_m = int(pq_m) if pq_m else auto_pq_m(s0_dim)
            if s0_dim % self.pq_m:
                raise ValueError(
                    f"pq_m={self.pq_m} does not divide the stage-0 dim "
                    f"{s0_dim}")
        else:
            self.pq_m = pq_m

    def _kernel_enabled(self) -> bool:
        if self.codec != "pq" or self.use_kernel is False:
            return False
        if self.use_kernel is True:
            return True
        return jax.default_backend() == "tpu"

    @staticmethod
    def _interpret() -> bool:
        return jax.default_backend() != "tpu"

    # -- build ---------------------------------------------------------------
    def build(
        self,
        db: Array,
        valid: Array,
        *,
        sq_prefix: Optional[Array] = None,
        stats: StoreStats,
    ) -> IndexState:
        # Code the whole buffer (static shape = capacity); the grid is fit
        # on live rows only, and dead/unpopulated rows are masked at search.
        if self.codec == "pq":
            from repro.core.pq import build_pq_index
            idx = build_pq_index(
                db, self.sched, m=self.pq_m, n_codes=self.pq_codes,
                n_iter=self.pq_iters, train_rows=self.pq_train_rows,
                valid=valid, seed=self.seed)
            n_coded = int(idx["codes"].shape[0])
        else:
            idx = build_quantized_index(db, self.sched, valid=valid)
            n_coded = int(idx["db0_q"].shape[0])
        tail_cap = self._tail_cap(stats.n_active)
        return IndexState.from_stats(
            self.name, stats,
            shape_key=(self.name, self.codec, n_coded, tail_cap,
                       self._kernel_enabled()),
            data={
                "idx": idx,
                "tail_cap": tail_cap,
                "codec": self.codec,
                # rows [0, coded_upto) carry codes on the state's frozen
                # grid: the built prefix, extended in place by
                # absorb_appends up to the block's capacity
                "coded_upto": min(stats.size, n_coded),
                "n_coded": n_coded,
            },
        )

    # -- incremental maintenance ----------------------------------------------
    def _tail_load(self, state: IndexState, stats: StoreStats) -> int:
        return stats.size - state.data["coded_upto"]

    def absorb_appends(
        self,
        state: IndexState,
        db: Array,
        valid: Array,
        *,
        sq_prefix: Optional[Array] = None,
        stats: StoreStats,
    ) -> None:
        """Encode appended rows against the state's frozen grid, in place.

        Runs between rebuilds at engine safe points: rows in
        ``[coded_upto, n_total)`` that still fit the code block are encoded
        with the build-time scale/codebooks and scattered into it — the
        grid refit waits for the next rebuild.  Rows past the block's
        capacity (the store grew) ride the tail window until then.
        Mutates ``state.data`` in place; every traced shape is preserved.
        """
        if not self.encode_appends:
            return
        upto = state.data["coded_upto"]
        n_new = min(stats.size, state.data["n_coded"]) - upto
        if n_new <= 0:
            return
        ids = jnp.asarray(pad_pow2(
            np.arange(upto, upto + n_new, dtype=np.int32)))
        idx = state.data["idx"]
        if self.codec == "pq":
            from repro.core.pq import pq_encode
            ds = idx["codebooks"].shape[0] * idx["codebooks"].shape[2]
            new = pq_encode(db[ids, :ds], idx["codebooks"])
            idx["codes"] = scatter_rows(idx["codes"], ids, new)
        else:
            ds = idx["db0_q"].shape[1]
            new, new_sq = int8_encode(db[ids, :ds], idx["scale0"])
            idx["db0_q"], idx["sq0"] = scatter_rows2(
                idx["db0_q"], idx["sq0"], ids, new, new_sq)
        state.data["coded_upto"] = upto + n_new

    def _tail_ids(self, state: IndexState, n_total: int) -> np.ndarray:
        """Static-shape (tail_cap,) window over rows past the coded prefix."""
        cap = state.data["tail_cap"]
        out = np.full((cap,), -1, np.int32)
        upto = state.data["coded_upto"]
        n_tail = min(max(n_total - upto, 0), cap)
        if n_tail:
            out[:n_tail] = np.arange(upto, upto + n_tail, dtype=np.int32)
        return out

    # -- search ---------------------------------------------------------------
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
        idx = state.data["idx"]
        tail = jnp.asarray(self._tail_ids(state, n_total))
        # adaptive degradation: the stage-0 codes are built at a fixed dim,
        # so the only per-dispatch lever here is the PQ oversample pool
        # (int8 has none — its stage-0 cost is pinned by the code block)
        pq_os = self._oversample(overrides)
        kw = dict(
            metric=self.metric,
            db=db,                       # rescore against the LIVE buffer
            valid=valid,
            # rows past the coded prefix have no codes: keep them out of
            # stage-0 ranking, reachable via the tail injection instead
            row_limit=jnp.asarray(state.data["coded_upto"]),
            extra_cand=tail,
        )
        if self.codec == "pq":
            from repro.core.pq import (
                pq_progressive_search,
                pq_progressive_search_kernel,
            )
            if self._kernel_enabled():
                scores, ids = pq_progressive_search_kernel(
                    q, idx, self.sched, block_m=self.kernel_block_m,
                    oversample=pq_os,
                    interpret=self._interpret(), **kw)
            else:
                scores, ids = pq_progressive_search(
                    q, idx, self.sched, oversample=pq_os, **kw)
        else:
            scores, ids = quantized_progressive_search(
                q, idx, self.sched, **kw)
        return scores[:, :k], ids[:, :k]

    def _oversample(self, overrides) -> int:
        if overrides is None:
            return self.pq_oversample
        return max(1, int(round(
            self.pq_oversample * overrides.oversample_frac)))

    def gauges(self, state: IndexState, stats: StoreStats):
        out = super().gauges(state, stats)
        n_coded = state.data["n_coded"]
        out.update({
            "coded_upto": float(state.data["coded_upto"]),
            "coded_frac": (min(stats.size, state.data["coded_upto"])
                           / stats.size if stats.size else 1.0),
            "code_block_rows": float(n_coded),
        })
        return out

    # -- persistence ----------------------------------------------------------
    # the idx's ``db`` entry is a snapshot of the store's own buffer — huge
    # and reconstructable: drop it at save, re-bind the live buffer at load
    _SAVE_SKIP = ("idx/db",)

    def _rebind_loaded(self, data, *, db, valid, sq_prefix=None) -> None:
        if data.get("codec") != self.codec:
            raise ValueError(
                f"checkpointed quantized index uses codec="
                f"{data.get('codec')!r}; this backend is configured for "
                f"{self.codec!r}")
        n_coded = data["n_coded"]
        if db.shape[0] < n_coded:
            raise ValueError(
                f"checkpointed code block covers {n_coded} buffer rows but "
                f"the store's capacity is {db.shape[0]}; the code block is "
                f"capacity-shaped — restore into a store grown to at least "
                f"the saved capacity")
        data["idx"]["db"] = db

    def describe(self) -> str:
        pq = f", pq_m={self.pq_m}" if self.codec == "pq" else ""
        return (
            f"QuantizedProgressiveBackend(codec={self.codec}{pq}, "
            f"rebuild_frac={self.rebuild_frac}, metric={self.metric}, "
            f"use_kernel={self.use_kernel})"
        )
