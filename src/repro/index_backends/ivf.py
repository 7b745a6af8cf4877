"""IVF-progressive backend: k-means coarse quantizer in front of the schedule.

Stage 0 stops scanning the whole buffer: queries probe the ``n_probe``
nearest centroids and only the probed lists' members are scored, then the
normal progressive rescore ladder runs on the survivors.  Two build-time
decisions drive the cost/recall profile:

* **Probe space** (``probe_dim``) — centroids are clustered, assigned, and
  probed in the *same* truncated space, so a query equal to a document
  ranks that document's cell exactly where the assignment did.  Probing is
  an (n_lists, d) matmul — tiny next to the member scan — so a wider probe
  space buys better cell ranking nearly for free.
* **Balanced assignment** (``balance_factor``) — the member table is dense
  (its width is the longest list), so unbounded nearest-centroid
  assignment makes every query pay the occupancy *skew* in padded
  candidate slots.  Lists are capacity-bounded at ``balance_factor`` times
  the mean occupancy (see `repro.core.ivf.balanced_assign`), trading a
  little displacement for a table width near the mean.

**Fused stage-0 kernel** (``use_kernel``): the probe+scan hot path can run
as the Pallas kernel `repro.kernels.ivf_scan` — probed lists' member rows
stream HBM→VMEM once (list-major slabs packed at build time) and the
stage-0 top-k never leaves VMEM, instead of the XLA gather → candidate
table → score matrix round trips.  ``'auto'`` picks the kernel on real TPUs
and the XLA path on CPU (where the kernel would run in the interpreter);
``True`` forces it everywhere (interpret mode off-TPU — the parity-tested
configuration).  ``stage0_dtype='int8'`` stores the member slabs as
per-dimension int8 codes (`repro.core.quant`'s grid), composing the
quantized and IVF backends: 4× less stage-0 HBM traffic on top of the
probed-list pruning, full-precision rescore unchanged.

Staleness: appended rows are **absorbed incrementally** at engine safe
points (``absorb_appends``): each new row goes to its nearest centroid's
list while that list has spare slots (``append_spare`` reserved per list at
build time); only rows whose list is full ride the tail window (see
``base.tail_ids``), so append-heavy workloads stop forcing early rebuilds.
Churn past ``rebuild_frac`` of the built corpus still triggers a full
re-cluster (assignment quality), and deletes only degrade list occupancy
(the validity mask keeps them unreturnable).  A rebuild drops tombstoned
rows from the lists entirely — the index side of compaction.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import progressive_search
from repro.core.ivf import (
    balanced_assign,
    ivf_progressive_search_kernel,
    ivf_progressive_search_sched,
    kmeans,
    pack_lists,
)
from repro.core import truncated as T
from repro.index_backends.base import (
    ChurnRebuildBackend,
    IndexState,
    StoreStats,
    register_backend,
)

Array = jax.Array


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_lists_donate(lists, lst, slot, ids):
    return lists.at[lst, slot].set(ids)


@jax.jit
def _scatter_lists_copy(lists, lst, slot, ids):
    return lists.at[lst, slot].set(ids)


@register_backend
class IVFProgressiveBackend(ChurnRebuildBackend):
    """Coarse-quantized candidate generation + progressive rescore."""

    name = "ivf"

    def __init__(
        self,
        sched,
        *,
        metric: str = "l2",
        block_n: int = 65536,
        n_lists: Optional[int] = None,
        n_probe: int = 12,
        probe_dim: Optional[int] = None,
        balance_factor: Optional[float] = 2.0,
        assign_m: int = 8,
        kmeans_iters: int = 10,
        train_rows: int = 131072,
        assign_block: int = 65536,
        rebuild_frac: float = 0.25,
        min_rebuild_rows: int = 64,
        tail_window: int = 512,
        min_index_rows: int = 64,
        append_spare: int = 8,
        use_kernel="auto",
        stage0_dtype: str = "float32",
        kernel_block_m: int = 128,
        pq_m: Optional[int] = None,
        pq_codes: int = 256,
        pq_iters: int = 10,
        pq_oversample: int = 4,
        seed: int = 0,
    ):
        """Args beyond the shared engine config:

        n_lists:        coarse-quantizer cells (None: ~n_live / 64, i.e. a
                        mean occupancy of 64 rows — candidate width then
                        stays roughly constant as the corpus grows — capped
                        at 4096 so k-means' per-iteration (rows, n_lists)
                        matrices stay bounded).
        train_rows:     k-means trains on at most this many sampled live
                        rows (the classic quantizer-training bound; the
                        assignment still covers every row).
        assign_block:   rows scored per tile when assigning — the
                        (rows, n_lists) score matrix never materializes for
                        the whole corpus at once.
        n_probe:        cells scanned per query.
        probe_dim:      clustering/probing dimensionality (None: the
                        schedule's max dim — probing is cheap, so rank
                        cells in the best space available).
        balance_factor: per-list capacity as a multiple of mean occupancy
                        (None: unbounded nearest-centroid assignment).
        assign_m:       centroid choices per row for balanced assignment.
        rebuild_frac / min_rebuild_rows / tail_window: see
                        ``ChurnRebuildBackend``.
        min_index_rows: below this live-row count, skip clustering and
                        serve the flat path (state flag) — exact and
                        cheaper than probing a near-empty table.
        append_spare:   free slots reserved per list at build time;
                        ``absorb_appends`` places appended rows there
                        (nearest centroid) between rebuilds, so only rows
                        whose list is full consume the tail window.  0
                        disables absorption (appends ride the tail only).
        use_kernel:     'auto' | True | False — stage-0 via the fused
                        Pallas probe+scan kernel ('auto': TPU only; True
                        forces it, interpret mode off-TPU; False: XLA).
        stage0_dtype:   'float32' | 'int8' | 'pq' member slabs for the
                        kernel scan (int8 composes `repro.core.quant`'s
                        codes — 4x less stage-0 traffic; 'pq' composes
                        `repro.core.pq`'s product-quantization codes —
                        pq_m bytes/row and a VMEM-resident ADC lookup
                        table, the fused probe+LUT-scan.  Both require
                        the kernel path).
        kernel_block_m: member rows per kernel step.
        pq_m:           'pq' only: subspaces per stage-0 row (None: aim
                        4-dim subspaces — `repro.core.pq.auto_pq_m`); must
                        divide the stage-0 dim.
        pq_codes:       'pq' only: centroids per subspace (<= 256).
        pq_iters:       'pq' only: k-means iterations per subspace.
        pq_oversample:  'pq' only: stage-0 survivor pool widens to
                        ``pq_oversample × k0`` (ADC noise is absorbed by
                        the full-precision rescore, which cuts it back).
        """
        super().__init__(
            sched, metric=metric, block_n=block_n,
            rebuild_frac=rebuild_frac, min_rebuild_rows=min_rebuild_rows,
            tail_window=tail_window,
        )
        self.n_lists = n_lists
        self.n_probe = int(n_probe)
        self.probe_dim = probe_dim
        self.balance_factor = balance_factor
        self.assign_m = int(assign_m)
        self.kmeans_iters = int(kmeans_iters)
        self.train_rows = int(train_rows)
        self.assign_block = int(assign_block)
        self.min_index_rows = int(min_index_rows)
        self.append_spare = int(append_spare)
        if use_kernel not in ("auto", True, False):
            raise ValueError(
                f"use_kernel must be 'auto'|True|False, got {use_kernel!r}")
        if stage0_dtype not in ("float32", "int8", "pq"):
            raise ValueError(
                f"stage0_dtype must be float32|int8|pq, got {stage0_dtype!r}")
        if use_kernel is True and metric != "l2":
            raise ValueError(
                "the fused IVF kernel scores L2 only; use metric='l2' or "
                "use_kernel='auto'/False")
        self.use_kernel = use_kernel
        self.stage0_dtype = stage0_dtype
        self.kernel_block_m = int(kernel_block_m)
        self.pq_codes = int(pq_codes)
        self.pq_iters = int(pq_iters)
        self.pq_oversample = max(1, int(pq_oversample))
        s0_dim = sched.stages[0].dim
        if stage0_dtype == "pq":
            from repro.core.pq import auto_pq_m
            self.pq_m = int(pq_m) if pq_m else auto_pq_m(s0_dim)
            if s0_dim % self.pq_m:
                raise ValueError(
                    f"pq_m={self.pq_m} does not divide the stage-0 dim "
                    f"{s0_dim}")
        else:
            self.pq_m = pq_m
        self.seed = int(seed)
        if stage0_dtype in ("int8", "pq") and not self._kernel_enabled():
            # coded member slabs only exist on the kernel path; silently
            # serving the f32 XLA path instead would report a traffic win
            # that never happens
            raise ValueError(
                f"stage0_dtype={stage0_dtype!r} packs member slabs for the "
                "fused kernel, which is disabled here (use_kernel="
                f"{use_kernel!r} on backend {jax.default_backend()!r}); "
                "pass use_kernel=True (interpret mode off-TPU) or "
                "stage0_dtype='float32'")

    def _kernel_enabled(self) -> bool:
        if self.use_kernel is False or self.metric != "l2":
            return False
        if self.use_kernel is True:
            return True
        return jax.default_backend() == "tpu"

    @staticmethod
    def _interpret() -> bool:
        return jax.default_backend() != "tpu"

    # -- build --------------------------------------------------------------
    def build(
        self,
        db: Array,
        valid: Array,
        *,
        sq_prefix: Optional[Array] = None,
        stats: StoreStats,
    ) -> IndexState:
        live = np.nonzero(np.asarray(valid[: stats.size]))[0] if stats.size else (
            np.zeros((0,), np.int64)
        )
        n_live = int(live.size)
        if n_live < self.min_index_rows:
            return IndexState.from_stats(
                self.name, stats,
                shape_key=(self.name, "flat-fallback"),
                data={"flat": True, "tail_cap": self._tail_cap(n_live)},
            )

        # auto n_lists snaps DOWN to a power of two: small corpus churn then
        # reproduces the same cell count (and thus the same traced shapes)
        # across rebuilds, so a state swap doesn't recompile every bucket
        auto = min(max(1, n_live // 64), 4096)
        n_lists = self.n_lists or 1 << (auto.bit_length() - 1)
        n_lists = min(n_lists, n_live)
        d_probe = self.probe_dim or self.sched.d_max

        def live_rows(sel):
            # probe-space rows of the live ids ``sel``, gathered per use: a
            # copy of every live row would double the store on the device
            return db[jnp.asarray(sel), :d_probe].astype(jnp.float32)

        # Train the quantizer on a bounded sample (assignment covers all
        # rows below): k-means holds a (rows, n_lists) matrix per iteration.
        rng = np.random.default_rng(self.seed)
        if n_live > self.train_rows:
            sample = np.sort(rng.choice(n_live, self.train_rows,
                                        replace=False))
            train = live_rows(live[sample])
        else:
            train = live_rows(live)
        cents = kmeans(train, n_lists, n_iter=self.kmeans_iters,
                       key=jax.random.PRNGKey(self.seed))
        del train
        # centroid norms are probe-time constants: cache them in the state
        # so no search call recomputes them
        cent_sq = jnp.sum(cents.astype(jnp.float32) ** 2, axis=-1)

        m = min(self.assign_m, n_lists)
        # rank cells with the serving metric so assignment and probing
        # agree on what "nearest cell" means; tile over rows so the
        # (rows, n_lists) score matrix stays O(assign_block * n_lists)
        score_fn = T._METRICS[self.metric]
        neg_parts, choice_parts = [], []
        for lo in range(0, n_live, self.assign_block):
            blk = live_rows(live[lo: lo + self.assign_block])
            neg_b, choices_b = jax.lax.top_k(-score_fn(blk, cents, cent_sq), m)
            # keep tiles on device: converting inside the loop would sync
            # per tile and serialize dispatch against compute
            neg_parts.append(neg_b[:, 0])
            choice_parts.append(choices_b)
        neg0, choices = jax.device_get(
            (jnp.concatenate(neg_parts), jnp.concatenate(choice_parts)))
        if self.balance_factor is None or n_lists == 1:
            assign = choices[:, 0]
        else:
            cap = max(1, int(math.ceil(
                self.balance_factor * n_live / n_lists)))
            order = np.argsort(-neg0)               # confident rows first

            def rank_rest(rows):
                return np.concatenate([
                    np.asarray(jnp.argsort(score_fn(
                        live_rows(live[rows[lo: lo + self.assign_block]]),
                        cents, cent_sq), axis=1))
                    for lo in range(0, rows.size, self.assign_block)])

            assign = balanced_assign(choices, order, n_lists, cap, rank_rest)

        # Dense -1-padded table of *global* doc ids via the shared packing
        # path; append_spare slots stay free for incremental absorption, and
        # the width rounds UP to a power of two (same shape-stability story
        # as n_lists; padding slots are -1 and score +inf)
        table = pack_lists(assign, n_lists, ids=live,
                           spare=self.append_spare, round_pow2=True)
        max_len = table.shape[1]
        list_fill = np.bincount(assign, minlength=n_lists).astype(np.int64)
        tail_cap = self._tail_cap(n_live)

        kernel_on = self._kernel_enabled()
        pack = None
        if kernel_on:
            from repro.core.ivf import _sq_col
            from repro.kernels.ivf_scan import pack_ivf_lists
            s0_dim = self.sched.stages[0].dim
            codebooks = None
            if self.stage0_dtype == "pq":
                # ADC codebooks are fit on live rows at the *stage-0* dim
                # (the space the slabs are scanned in), on the same bounded
                # sample budget as the coarse quantizer
                from repro.core.pq import train_pq
                tr = live
                if tr.size > self.train_rows:
                    tr = np.sort(rng.choice(tr, self.train_rows,
                                            replace=False))
                codebooks = train_pq(
                    db[jnp.asarray(tr)][:, :s0_dim],
                    m=self.pq_m, n_codes=self.pq_codes,
                    n_iter=self.pq_iters,
                    key=jax.random.PRNGKey(self.seed + 1))
            pack = pack_ivf_lists(
                db, jnp.asarray(table), dim=s0_dim,
                db_sq_at_dim=_sq_col(sq_prefix, self.dims, s0_dim),
                dtype=self.stage0_dtype, block_m=self.kernel_block_m,
                pq_codebooks=codebooks,
            )
        return IndexState.from_stats(
            self.name, stats,
            shape_key=(self.name, n_lists, max_len, tail_cap,
                       kernel_on, self.stage0_dtype),
            data={
                "centroids": cents,                 # (n_lists, d_probe) f32
                "cent_sq": cent_sq,                 # (n_lists,) f32 cached
                "lists": jnp.asarray(table),        # (n_lists, max_len) i32
                "list_fill": list_fill,             # (n_lists,) host counts
                "absorb_upto": stats.size,          # rows examined so far
                "tail_pending": np.zeros((0,), np.int32),
                "pack": pack,                       # kernel member slabs
                "n_lists": n_lists,
                "max_len": max_len,
                "tail_cap": tail_cap,
            },
        )

    # -- incremental maintenance -------------------------------------------
    def _tail_load(self, state: IndexState, stats: StoreStats) -> int:
        if state.data.get("flat"):
            return super()._tail_load(state, stats)
        return (len(state.data["tail_pending"])
                + (stats.size - state.data["absorb_upto"]))

    def absorb_appends(
        self,
        state: IndexState,
        db: Array,
        valid: Array,
        *,
        sq_prefix: Optional[Array] = None,
        stats: StoreStats,
    ) -> None:
        """Assign appended rows to their nearest centroid's spare slots.

        Runs between rebuilds at engine safe points: each row in
        ``[absorb_upto, n_total)`` joins its nearest list if that list has a
        free slot, otherwise it stays in the tail window (``tail_pending``).
        Mutates ``state.data`` in place; every traced shape is preserved —
        only table/slab *contents* change, so no dispatch recompiles.
        """
        if state.data.get("flat"):
            return
        if self.append_spare == 0:
            # incremental maintenance disabled: appended rows ride the tail
            # window until the next rebuild (the pre-absorption behavior,
            # and what the tail-overflow hard-bound tests exercise)
            return
        n_total = stats.size
        upto = state.data["absorb_upto"]
        if n_total <= upto:
            # no new rows — deletes may have freed tail-window capacity, but
            # only re-check liveness when something was actually deleted
            # since the last prune: this branch runs on every dispatch and
            # the gather below is a device round trip under engine.lock
            pending = state.data["tail_pending"]
            if (pending.size
                    and state.data.get("pruned_at_deleted")
                    != stats.total_deleted):
                alive = np.asarray(valid[jnp.asarray(pending)])
                state.data["tail_pending"] = pending[alive]
                state.data["pruned_at_deleted"] = stats.total_deleted
            return
        new_ids = np.arange(upto, n_total, dtype=np.int64)
        cents = state.data["centroids"]
        d_probe = cents.shape[1]
        score_fn = T._METRICS[self.metric]
        rows = db[jnp.asarray(new_ids), :d_probe].astype(jnp.float32)
        nearest = np.asarray(jnp.argmin(
            score_fn(rows, cents, state.data["cent_sq"]), axis=1))

        lists = state.data["lists"]
        pack = state.data["pack"]
        fill = state.data["list_fill"]
        max_len = state.data["max_len"]
        acc_ids, acc_lists, acc_slots, rejected = [], [], [], []
        for rid, lst in zip(new_ids, nearest):
            lst = int(lst)
            if fill[lst] < max_len:
                acc_ids.append(rid)
                acc_lists.append(lst)
                acc_slots.append(int(fill[lst]))
                fill[lst] += 1
            else:
                rejected.append(rid)
        if acc_ids:
            # jitted scatter with buffer donation off-CPU: absorbing a few
            # rows must update the device tables in place, not copy them
            # (batch padded to a power of two so burst sizes don't retrace)
            from repro.kernels.ivf_scan import _pad_pow2, update_pack
            scatter = (_scatter_lists_copy
                       if jax.default_backend() == "cpu"
                       else _scatter_lists_donate)
            lists = scatter(
                lists,
                jnp.asarray(_pad_pow2(np.asarray(acc_lists, np.int32))),
                jnp.asarray(_pad_pow2(np.asarray(acc_slots, np.int32))),
                jnp.asarray(_pad_pow2(np.asarray(acc_ids, np.int32))))
            if pack is not None:
                dests = (np.asarray(acc_lists, np.int64) * pack["max_len"]
                         + np.asarray(acc_slots, np.int64))
                pack = update_pack(pack, db, np.asarray(acc_ids, np.int32),
                                   dests)
        pending = np.concatenate(
            [state.data["tail_pending"],
             np.asarray(rejected, np.int32)]).astype(np.int32)
        if pending.size:
            # tombstoned pending rows would hold window capacity forever;
            # the validity mask already makes them unreturnable, so drop them
            alive = np.asarray(valid[jnp.asarray(pending)])
            pending = pending[alive]
        state.data.update(
            lists=lists, pack=pack, list_fill=fill,
            absorb_upto=n_total, tail_pending=pending,
            pruned_at_deleted=stats.total_deleted,
        )

    def _tail_ids(self, state: IndexState, n_total: int) -> np.ndarray:
        """Static-shape (tail_cap,) window: pending + not-yet-absorbed ids."""
        cap = state.data["tail_cap"]
        out = np.full((cap,), -1, np.int32)
        ids = np.concatenate([
            state.data["tail_pending"],
            np.arange(state.data["absorb_upto"], n_total, dtype=np.int32),
        ])[:cap]
        out[: ids.size] = ids
        return out

    # -- search -------------------------------------------------------------
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
        # adaptive degradation knobs, all static per dispatch (one extra
        # compiled program per level, pre-warmed by engine.warmup): probe
        # fewer lists, shrink the PQ oversample pool, and — on the paths
        # whose stage-0 dim isn't baked into packed slabs — enter the
        # progressive ladder at a lower d_start rung
        sched, n_probe, pq_os = self._apply_overrides(state, overrides)
        if state.data.get("flat"):
            scores, ids = progressive_search(
                q, db, sched,
                sq_prefix=sq_prefix, index_dims=self.dims,
                valid=valid, block_n=min(self.block_n, db.shape[0]),
                metric=self.metric,
            )
            return scores[:, :k], ids[:, :k]
        tail = jnp.asarray(self._tail_ids(state, n_total))
        if state.data["pack"] is not None:
            scores, ids = ivf_progressive_search_kernel(
                q, db, state.data["centroids"], state.data["lists"],
                self.sched, n_probe=n_probe,
                valid=valid, sq_prefix=sq_prefix, index_dims=self.dims,
                extra_cand=tail, metric=self.metric,
                cent_sq=state.data["cent_sq"], pack=state.data["pack"],
                pq_oversample=pq_os,
                interpret=self._interpret(),
            )
        else:
            scores, ids = ivf_progressive_search_sched(
                q, db, state.data["centroids"], state.data["lists"],
                sched, n_probe=n_probe,
                valid=valid, sq_prefix=sq_prefix, index_dims=self.dims,
                extra_cand=tail, metric=self.metric,
                cent_sq=state.data["cent_sq"],
            )
        return scores[:, :k], ids[:, :k]

    def _apply_overrides(self, state: IndexState, overrides):
        """Resolve (sched, n_probe, pq_oversample) for one dispatch.

        ``overrides.sched`` only applies where the stage-0 dim is not
        frozen into a build artifact (the flat fallback and the XLA sched
        path); packed int8/PQ member slabs pin their stage-0 dim/codes at
        build time, so those paths degrade via n_probe/oversample alone.
        """
        pq_os = self.pq_oversample if self.stage0_dtype == "pq" else 1
        if state.data.get("flat"):
            n_probe = self.n_probe
        else:
            n_probe = min(self.n_probe, state.data["n_lists"])
        if overrides is None:
            return self.sched, n_probe, pq_os
        sched = self.sched if overrides.sched is None else overrides.sched
        if not state.data.get("flat"):
            n_probe = min(
                max(1, int(round(self.n_probe * overrides.n_probe_frac))),
                state.data["n_lists"])
        if pq_os > 1:
            pq_os = max(1, int(round(pq_os * overrides.oversample_frac)))
        return sched, n_probe, pq_os

    def gauges(self, state: IndexState, stats: StoreStats):
        out = super().gauges(state, stats)
        if state.data.get("flat"):
            return out
        n_lists = state.data["n_lists"]
        max_len = state.data["max_len"]
        fill = state.data["list_fill"]
        out.update({
            "n_lists": float(n_lists),
            "list_fill_frac": (float(fill.sum()) / (n_lists * max_len)
                               if n_lists * max_len else 0.0),
            "append_spare_used": float(
                max(0, int(fill.sum()) - state.built_active)),
            "tail_pending": float(len(state.data["tail_pending"])),
            "absorbed_rows": float(
                state.data["absorb_upto"] - state.built_size),
        })
        return out

    def describe(self) -> str:
        return (
            f"IVFProgressiveBackend(n_lists={self.n_lists or 'auto'}, "
            f"n_probe={self.n_probe}, rebuild_frac={self.rebuild_frac}, "
            f"metric={self.metric}, use_kernel={self.use_kernel}, "
            f"stage0_dtype={self.stage0_dtype})"
        )
