"""IVF-Flat approximate search — beyond-paper, TPU-idiomatic ANN comparator.

The paper compared against HNSW and noted its graph construction cost;
HNSW's pointer-chasing greedy graph walk has no efficient TPU analogue
(serial, data-dependent control flow — see DESIGN.md §Hardware-adaptation).
The TPU-native equivalent of "prune the search space before exact scoring"
is an inverted-file (IVF) index: k-means coarse quantizer + per-list exact
scan, which is pure matmul + gather and therefore maps onto the MXU.

It composes with progressive search: probing can run at a truncated
dimensionality and the final rescore at full dims — `ivf_progressive_search`
below — which is the paper's "future work: integration with ANN" realized.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import truncated as T
from repro.core.schedule import ProgressiveSchedule

Array = jax.Array


def balanced_assign(
    choices: np.ndarray,
    confidence_order: np.ndarray,
    n_lists: int,
    cap: int,
    rank_rest: Optional[Callable[[np.ndarray], np.ndarray]] = None,
) -> np.ndarray:
    """Capacity-bounded list assignment (host-side, build time).

    Plain nearest-centroid assignment over real corpora is heavily skewed
    (k-means cells routinely reach 5-10x the mean occupancy), and the IVF
    member table is dense: its width is the *longest* list, so every query
    pays the skew in padded candidate slots.  Bounding every list at ``cap``
    members keeps the table width — and therefore per-query scan cost —
    near the mean instead of the max.

    Rows are admitted to their most-preferred list with free capacity,
    confident rows first (a row whose nearest centroid is far away loses
    little by being displaced to its 2nd/3rd choice; a row close to its
    centroid should stay).  Rows that find all ``m`` choices full take the
    first list with room in the full order ``rank_rest`` gives them.  A
    caller whose lists can fill should rank by distance: a few hub
    centroids that are every row's first choices fill up early in high
    dimensions, and a row spilled to a far list is one no probe near it
    will look at.  Every list stays bounded at ``cap``.

    Args:
      choices:          (N, m) int centroid preference order per row.
      confidence_order: (N,) row indices, most-confident first.
      n_lists:          number of lists.
      cap:              max members per list; needs n_lists * cap >= N.
      rank_rest:        maps (R,) row indices to their (R, n_lists) full
                        centroid preference order (default: list index
                        order).

    Returns:
      (N,) int32 list assignment.
    """
    n = choices.shape[0]
    if n_lists * cap < n:
        raise ValueError(f"cap {cap} x {n_lists} lists cannot hold {n} rows")
    assign = np.full(n, -1, np.int32)
    counts = np.zeros(n_lists, np.int64)

    def admit(prefs, rows):
        # rows (R,) in confidence order, prefs (R, w) their preferences;
        # returns the rows that no column admitted, still in order
        for j in range(prefs.shape[1]):
            if rows.size == 0:
                break
            pref = prefs[:, j]
            # stable-sort by list, keeping confidence order within each
            # list, then admit each list's first (cap - occupancy) rows
            by_list = np.argsort(pref, kind="stable")
            pref_sorted = pref[by_list]
            group_start = np.searchsorted(pref_sorted, pref_sorted)
            pos_in_group = np.arange(rows.size) - group_start
            ok = pos_in_group < (cap - counts[pref_sorted])
            assign[rows[by_list[ok]]] = pref_sorted[ok]
            np.add.at(counts, pref_sorted[ok], 1)
            keep = np.sort(by_list[~ok])                # restore order
            rows, prefs = rows[keep], prefs[keep]
        return rows

    if rank_rest is None:
        def rank_rest(rows):
            return np.broadcast_to(np.arange(n_lists), (rows.size, n_lists))

    remaining = admit(choices[confidence_order], confidence_order)
    if remaining.size:
        admit(rank_rest(remaining), remaining)
    return assign


def pack_lists(
    assign: np.ndarray,
    n_lists: int,
    *,
    ids: Optional[np.ndarray] = None,
    spare: int = 0,
    round_pow2: bool = False,
) -> np.ndarray:
    """Pack a (N,) list assignment into a dense -1-padded member table.

    The single packing path shared by `build_ivf` and the engine's IVF
    backend (one stable argsort, not a per-list scan — n_lists scales with
    N, so a scan per list would make builds quadratic).

    Args:
      assign:     (N,) int list assignment.
      n_lists:    number of lists.
      ids:        (N,) global ids to store (default ``arange(N)``).
      spare:      reserved free slots per list beyond the max occupancy
                  (incremental appends land here between rebuilds).
      round_pow2: round the table width up to a power of two (shape
                  stability across rebuilds keeps state swaps compile-free).

    Returns:
      (n_lists, width) int32 member table, -1 padded.
    """
    n = len(assign)
    if ids is None:
        ids = np.arange(n)
    counts = np.bincount(assign, minlength=n_lists)
    width = max(int(counts.max()) if n else 0, 0) + int(spare)
    width = max(width, 1)
    if round_pow2:
        width = 1 << (width - 1).bit_length()
    order = np.argsort(assign, kind="stable")
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    table = np.full((n_lists, width), -1, np.int32)
    sorted_lists = assign[order]
    table[sorted_lists, np.arange(n) - starts[sorted_lists]] = ids[order]
    return table


@functools.partial(jax.jit, static_argnames=("n_lists", "n_iter"))
def kmeans(db: Array, n_lists: int, *, n_iter: int = 10, key=None) -> Array:
    """Lloyd's k-means over db rows. Returns (n_lists, D) centroids."""
    if key is None:
        key = jax.random.PRNGKey(0)
    n = db.shape[0]
    init_idx = jax.random.choice(key, n, (n_lists,), replace=False)
    cents = db[init_idx].astype(jnp.float32)

    def step(cents, _):
        s = T.l2_scores(db.astype(jnp.float32), cents)   # (N, n_lists)
        assign = jnp.argmin(s, axis=1)
        one_hot = jax.nn.one_hot(assign, n_lists, dtype=jnp.float32)
        counts = one_hot.sum(axis=0)                     # (n_lists,)
        sums = one_hot.T @ db.astype(jnp.float32)        # (n_lists, D)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None], cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=n_iter)
    return cents


def build_ivf(
    db: Array, n_lists: int, *, key=None, n_iter: int = 10
) -> Dict[str, Array]:
    """Build an IVF index: centroids + padded per-list member tables.

    Lists are padded to the max list length so the structure is a dense
    (n_lists, max_len) int32 table — static shapes for XLA, -1 padding.
    """
    cents = kmeans(db, n_lists, key=key, n_iter=n_iter)
    s = T.l2_scores(db.astype(jnp.float32), cents)
    # Host-side packing (build time, not query time) through the same
    # assignment + packing path the engine backend uses: balanced_assign
    # with an unbounded cap IS plain nearest-centroid assignment, and
    # pack_lists is the one dense-table builder — the two paths can't drift.
    choices = np.asarray(jnp.argmin(s, axis=1))[:, None]
    n = choices.shape[0]
    assign_np = balanced_assign(choices, np.arange(n), n_lists, cap=n)
    table = pack_lists(assign_np, n_lists)
    return {
        "centroids": cents,
        "lists": jnp.asarray(table),
        "assign": jnp.asarray(assign_np.astype(np.int32)),
    }


@functools.partial(jax.jit, static_argnames=("n_probe", "k", "dim"))
def ivf_search(
    q: Array, db: Array, ivf: Dict[str, Array], *, n_probe: int, k: int,
    dim: int | None = None, valid: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """IVF-Flat search: probe ``n_probe`` nearest lists, exact-scan their members.

    Args:
      q:     (Q, D) queries.  dim: optional truncation for probing+scan.
      valid: optional (N,) bool row mask (mutable corpora): candidates whose
             bit is clear are scored +inf and can never be returned.
    Returns:
      ((Q, k) scores, (Q, k) int32 indices).
    """
    d = dim or db.shape[1]
    qd = q[:, :d]
    cents = ivf["centroids"][:, :d]
    cs = T.l2_scores(qd, cents)                      # (Q, n_lists)
    _, probe = jax.lax.top_k(-cs, n_probe)           # (Q, n_probe)
    members = ivf["lists"][probe]                    # (Q, n_probe, max_len)
    cand = members.reshape(q.shape[0], -1)           # (Q, n_probe*max_len)
    return T.rescore_candidates(qd, db[:, :d], cand, dim=d, k=k, valid=valid)


@functools.partial(jax.jit, static_argnames=("n_probe", "k", "d_probe", "d_final"))
def ivf_progressive_search(
    q: Array,
    db: Array,
    ivf: Dict[str, Array],
    *,
    n_probe: int,
    k: int,
    d_probe: int,
    d_final: int,
    valid: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """IVF probing at truncated dims + exact rescore at full dims.

    Realizes the paper's future-work suggestion: ANN candidate generation
    composed with progressive dimensional refinement.
    """
    _, cand = ivf_search(q, db, ivf, n_probe=n_probe, k=k * 8,
                         dim=d_probe, valid=valid)
    return T.rescore_candidates(q, db, cand, dim=d_final, k=k, valid=valid)


@functools.partial(
    jax.jit, static_argnames=("sched", "n_probe", "index_dims", "metric")
)
def ivf_progressive_search_sched(
    q: Array,
    db: Array,
    centroids: Array,
    lists: Array,
    sched: ProgressiveSchedule,
    *,
    n_probe: int,
    valid: Optional[Array] = None,
    sq_prefix: Optional[Array] = None,
    index_dims: Optional[tuple] = None,
    extra_cand: Optional[Array] = None,
    metric: str = "l2",
    cent_sq: Optional[Array] = None,
) -> Tuple[Array, Array]:
    """Full progressive schedule with IVF probing replacing the stage-0 scan.

    Probing runs at the centroids' own dimensionality (the space they were
    clustered in — build/search consistency keeps an exact-match query
    probing the cell its document was assigned to); probed members — plus
    optional ``extra_cand`` rows, e.g. the engine's un-indexed tail window —
    are rescored through the schedule's stages at full precision, exactly
    like the flat path after stage 0.

    Args:
      centroids:  (n_lists, d_probe) coarse quantizer; d_probe <= q dim.
      lists:      (n_lists, max_len) int32 member table, -1 padded.
      extra_cand: optional (E,) int32 ids injected into every query's
                  candidate list (-1 padded); must be disjoint from list
                  members so the final top-k carries no duplicate ids.
      valid:      optional (N,) bool row mask threaded through every stage.
      cent_sq:    optional (n_lists,) precomputed centroid squared norms —
                  built backends cache these so probing doesn't recompute
                  them per search call.

    Named scopes: ``stage0`` (``stage0/probe``: centroid scores and top-k;
    then the member gather and the stage-0 scoring of the members) and
    ``rescore`` (the ladder's later stages).
    """
    from repro.core.progressive import rescore_ladder

    s0 = sched.stages[0]
    score_fn = T._METRICS[metric]

    with jax.named_scope("stage0"):
        with jax.named_scope("probe"):
            d_probe = centroids.shape[1]
            cs = score_fn(q[:, :d_probe], centroids, cent_sq)  # (Q, n_lists)
            _, probe = jax.lax.top_k(-cs, min(n_probe, centroids.shape[0]))
        cand = lists[probe].reshape(q.shape[0], -1)   # (Q, n_probe*max_len)
        cand = T.inject_candidates(cand, extra_cand)
        if cand.shape[1] < s0.k:
            # top_k needs k <= C; -1 columns score +inf and change nothing
            cand = jnp.pad(cand, ((0, 0), (0, s0.k - cand.shape[1])),
                           constant_values=-1)
        # the probed members replace the stage-0 full scan: stage 0 is a
        # rescore of them at its own dim, and every later stage follows
        scores, cand = T.rescore_candidates(
            q, db, cand, dim=s0.dim, k=s0.k,
            db_sq_at_dim=_sq_col(sq_prefix, index_dims, s0.dim),
            valid=valid, metric=metric,
        )
    return rescore_ladder(
        q, db, cand, sched.stages[1:],
        sq_prefix=sq_prefix, index_dims=index_dims,
        valid=valid, metric=metric, scores=scores,
    )


def _sq_col(sq_prefix, index_dims, dim: int):
    """Static lookup of the cached prefix-norm column at ``dim``, if any."""
    if sq_prefix is None or index_dims is None:
        return None
    dims = tuple(int(x) for x in index_dims)
    if int(dim) not in dims:
        return None
    return sq_prefix[:, dims.index(int(dim))]


@functools.partial(
    jax.jit,
    static_argnames=("sched", "n_probe", "index_dims", "metric",
                     "pack_meta", "pq_oversample", "interpret"),
)
def _kernel_search_jit(
    q, db, centroids, lists, pack_rows, pack_sq, pack_scale,
    pack_codebooks, pack_cent_sq,
    valid, sq_prefix, extra_cand, cent_sq, sched,
    *, n_probe, index_dims, metric, pack_meta, pq_oversample, interpret,
):
    """The fused IVF program.  Named scopes: ``stage0`` with ``probe``
    (centroid scores and top-k), ``member_mask`` (the per-query id table:
    the probed lists' member ids, with the validity gather over those
    slots alone, or over the whole member table once when the probes of
    the batch reach as many slots), ``scan`` (the fused kernel) and
    ``tail`` (the un-indexed rows); then ``rescore`` (the ladder)."""
    from repro.kernels.ivf_scan import ivf_scan_topk, probed_ids
    from repro.kernels.pq_scan import pq_ivf_scan_topk
    from repro.core.progressive import rescore_ladder

    s0 = sched.stages[0]
    with jax.named_scope("stage0"):
        with jax.named_scope("probe"):
            d_probe = centroids.shape[1]
            cs = T._METRICS[metric](q[:, :d_probe], centroids, cent_sq)
            _, probe = jax.lax.top_k(-cs, min(n_probe, centroids.shape[0]))

        # mask every unreturnable slot the probes scan to -1 BEFORE the
        # kernel: list padding is already -1, tombstoned rows come from the
        # live validity bits (the packed member vectors are a build-time
        # snapshot), and valid is per-dispatch data, so this cannot be cached
        with jax.named_scope("member_mask"):
            member_ids = probed_ids(lists, probe, valid)

        pack = {
            "rows": pack_rows, "sq": pack_sq, "scale": pack_scale,
            "codebooks": pack_codebooks, "cent_sq": pack_cent_sq,
            "dim": pack_meta[0], "max_len": pack_meta[1],
            "block_m": pack_meta[2], "dtype": pack_meta[3],
        }
        with jax.named_scope("scan"):
            if pack_meta[3] == "pq":
                # oversampled survivor pool: the classic PQ remedy for ADC
                # ranking noise — the full-precision rescore ladder cuts it
                # back
                k0_eff = s0.k * pq_oversample
                scores, cand = pq_ivf_scan_topk(
                    q, probe, member_ids, pack, k=k0_eff,
                    interpret=interpret)
            else:
                k0_eff = s0.k
                scores, cand = ivf_scan_topk(
                    q, probe, member_ids, pack, k=k0_eff,
                    interpret=interpret)

        if extra_cand is not None:
            # the un-indexed tail window competes in stage 0 exactly as the
            # XLA path's inject_candidates placement: rescore the (few) tail
            # rows at the stage-0 dim and fold them into the kernel's top-k
            with jax.named_scope("tail"):
                e = extra_cand.shape[0]
                tail_tbl = jnp.broadcast_to(
                    extra_cand[None, :], (q.shape[0], e))
                # keep as many tail survivors as the (possibly oversampled)
                # pool can seat — capping at s0.k would let coded rows
                # crowd appended rows out of pool slots they outscore
                ts, ti = T.rescore_candidates(
                    q, db, tail_tbl, dim=s0.dim, k=min(k0_eff, e),
                    db_sq_at_dim=_sq_col(sq_prefix, index_dims, s0.dim),
                    valid=valid, metric=metric,
                )
                cat_s = jnp.concatenate([scores, ts], axis=1)
                cat_i = jnp.concatenate([cand, ti], axis=1)
                neg, pos = jax.lax.top_k(-cat_s, k0_eff)
                scores = -neg
                cand = jnp.take_along_axis(cat_i, pos, axis=1)

    return rescore_ladder(
        q, db, cand, sched.stages[1:],
        sq_prefix=sq_prefix, index_dims=index_dims,
        valid=valid, metric=metric, scores=scores,
    )


def ivf_progressive_search_kernel(
    q: Array,
    db: Array,
    centroids: Array,
    lists: Array,
    sched: ProgressiveSchedule,
    *,
    n_probe: int,
    valid: Optional[Array] = None,
    sq_prefix: Optional[Array] = None,
    index_dims: Optional[tuple] = None,
    extra_cand: Optional[Array] = None,
    metric: str = "l2",
    cent_sq: Optional[Array] = None,
    pack: Optional[Dict] = None,
    block_m: int = 128,
    pq_oversample: int = 1,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """`ivf_progressive_search_sched` with the fused Pallas stage-0 kernel.

    Same signature and same results (identical top-k id sets under fixed
    probes — the parity contract `tests/test_kernels.py` enforces), but
    stage 0 runs `repro.kernels.ivf_scan.ivf_scan_topk` — or, for
    ``dtype='pq'`` packs, `repro.kernels.pq_scan.pq_ivf_scan_topk` (the
    fused probe+LUT-scan: per-query ADC tables stay VMEM-resident while
    M-byte code slabs stream) — so probed lists' member rows stream
    HBM→VMEM once and the top-k never leaves VMEM, instead of the XLA
    gather → materialized candidate table → score matrix round trips.  The
    tail ``extra_cand`` window is rescored at the stage-0 dim and merged
    into the kernel's top-k, so injected rows compete exactly where
    `inject_candidates` puts them on the XLA path.

    Extra args over the sched path:
      pack:      `pack_ivf_lists` build artifact (member slabs at the
                 stage-0 dim; pass the cached one from backend state — when
                 None it is packed on the fly, which costs a full gather).
      block_m:   member rows per kernel step (on-the-fly packs only).
      pq_oversample: 'pq' packs only — stage-0 survivor pool widens to
                 ``pq_oversample × k0`` (ADC ranking noise is absorbed by
                 the full-precision rescore, which cuts the pool back).
      interpret: run the kernel in interpret mode (CPU validation).
    """
    if metric != "l2":
        raise ValueError(
            f"the fused IVF kernel scores L2 only, got metric={metric!r} "
            f"(use ivf_progressive_search_sched)"
        )
    s0 = sched.stages[0]
    if pack is None:
        from repro.kernels.ivf_scan import pack_ivf_lists
        pack = pack_ivf_lists(
            db, lists, dim=s0.dim,
            db_sq_at_dim=_sq_col(sq_prefix, index_dims, s0.dim),
            block_m=block_m,
        )
    pack_meta = (pack["dim"], pack["max_len"], pack["block_m"], pack["dtype"])
    return _kernel_search_jit(
        q, db, centroids, lists, pack["rows"], pack["sq"], pack["scale"],
        pack.get("codebooks"), pack.get("cent_sq"),
        valid, sq_prefix, extra_cand, cent_sq, sched,
        n_probe=n_probe, index_dims=index_dims, metric=metric,
        pack_meta=pack_meta, pq_oversample=pq_oversample,
        interpret=interpret,
    )
