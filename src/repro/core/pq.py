"""Product-quantized stage 0 — the compression frontier past int8.

The paper's insight is that early search stages only need a *cheap sketch*
of each vector.  The repo already exploits the dimensionality axis
(truncated stage 0) and the precision axis (int8 stage 0); product
quantization (Jégou et al., the FAISS IVF-PQ workhorse) pushes the sketch
further: the stage-0 block is split into ``M`` subspaces of ``dsub = Ds/M``
dims, each k-means-quantized to ``C ≤ 256`` centroids, so a row's sketch is
``M`` uint8 codes — **M bytes/row** against ``Ds`` for int8 and ``4·Ds``
for f32.  Queries never decode rows: an **asymmetric-distance (ADC)**
lookup table of the query's distance to every centroid of every subspace
(``(M, C)`` floats, VMEM-resident in the fused kernel) turns scoring a row
into ``M`` table lookups, and the full-precision progressive rescore
absorbs the quantization noise exactly the way it absorbs truncation noise.

Rank-equivalence convention: like every scoring path in this repo, ADC
tables drop the per-query ``‖q‖²`` constant — ``lut[m, c] = ‖c‖² − 2·q_m·c``
— so ADC sums are directly comparable with `truncated.l2_scores` /
`rescore_candidates` outputs and exact tail-window rescores can merge into
a PQ top-k without a unit mismatch.

    idx = build_pq_index(db, sched, m=8)
    scores, ids = pq_progressive_search(q, idx, sched)
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


def auto_pq_m(d0: int) -> int:
    """Default subspace count for a ``d0``-dim stage-0 block: aim dsub = 4.

    ``d0 // 4`` when that divides evenly; otherwise a single subspace —
    coarse, but the progressive rescore runs at full precision either way,
    and an explicit ``pq_m`` is always available.  Codes cover only the
    stage-0 prefix while the full-precision rows stay resident for the
    rescore, so 4-dim subspaces cost a row a few more bytes of a budget the
    rescore store dominates.  What they buy is the stage-0 pool: on
    topical data the 256 codes of an 8-dim subspace settle on the topic
    centres, and at the paper deployment's 3584 dims cut to 262,144 rows
    (``chip_smoke.py``) 8-dim codes left the source row of 1 in 32 noisy
    copies outside the oversampled pool.
    """
    if d0 >= 8 and d0 % 4 == 0:
        return d0 // 4
    return 1


def pq_dims(codebooks: Array) -> Tuple[int, int, int]:
    """(M, C, dsub) of a codebook tensor."""
    m, c, dsub = codebooks.shape
    return int(m), int(c), int(dsub)


def pq_cent_sq(codebooks: Array) -> Array:
    """(M, C) squared centroid norms — the ADC tables' constant term."""
    cb = codebooks.astype(jnp.float32)
    return jnp.sum(cb * cb, axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "n_codes", "n_iter"))
def train_pq(
    x: Array, *, m: int, n_codes: int = 256, n_iter: int = 10, key=None
) -> Array:
    """Train PQ codebooks: independent k-means per subspace.

    Args:
      x:       (N, Ds) training rows (live corpus rows; Ds % m == 0).
      m:       subspace count.
      n_codes: centroids per subspace (≤ 256 so codes fit uint8).
      n_iter:  Lloyd iterations.
      key:     PRNG key (init sampling).

    Returns:
      (m, n_codes, Ds//m) float32 codebooks.

    Subspaces are fit sequentially (``lax.map``) so peak memory is one
    (N, n_codes) assignment matrix, not m of them.  When N < n_codes the
    init samples with replacement — duplicate centroids are harmless
    (encoding ties break to the lowest code) and keep every shape static
    across corpus sizes.
    """
    if n_codes > 256:
        raise ValueError(f"n_codes must be <= 256 (uint8 codes), got {n_codes}")
    n, ds = x.shape
    if ds % m:
        raise ValueError(f"stage-0 dim {ds} is not divisible by pq m={m}")
    if key is None:
        key = jax.random.PRNGKey(0)
    dsub = ds // m
    subs = x.astype(jnp.float32).reshape(n, m, dsub).transpose(1, 0, 2)
    keys = jax.random.split(key, m)
    replace = n < n_codes

    def fit(args):
        sub, k = args                                  # (N, dsub)
        init = jax.random.choice(k, n, (n_codes,), replace=replace)
        cents = sub[init]

        def step(c, _):
            s = T.l2_scores(sub, c)                    # (N, n_codes)
            a = jnp.argmin(s, axis=1)
            oh = jax.nn.one_hot(a, n_codes, dtype=jnp.float32)
            counts = oh.sum(axis=0)
            sums = oh.T @ sub
            new = jnp.where(counts[:, None] > 0,
                            sums / jnp.maximum(counts, 1.0)[:, None], c)
            return new, None

        cents, _ = jax.lax.scan(step, cents, None, length=n_iter)
        return cents

    return jax.lax.map(fit, (subs, keys))


@jax.jit
def _encode_block(x: Array, codebooks: Array, cent_sq: Array) -> Array:
    m, _, dsub = codebooks.shape
    xs = x.astype(jnp.float32).reshape(x.shape[0], m, dsub)
    ip = jnp.einsum("nmd,mcd->nmc", xs, codebooks,
                    preferred_element_type=jnp.float32)
    s = cent_sq[None, :, :] - 2.0 * ip                 # rank-equivalent
    return jnp.argmin(s, axis=-1).astype(jnp.uint8)


def pq_encode(x: Array, codebooks: Array, *, block_n: int = 8192) -> Array:
    """Encode rows to (N, M) uint8 codes (nearest centroid per subspace).

    Blocked over rows so the (block, M, C) assignment scores never
    materialize for the whole corpus at once (build/absorb time, host loop).
    """
    cent_sq = pq_cent_sq(codebooks)
    n = x.shape[0]
    if n <= block_n:
        return _encode_block(x, codebooks, cent_sq)
    parts = [
        _encode_block(x[lo: lo + block_n], codebooks, cent_sq)
        for lo in range(0, n, block_n)
    ]
    return jnp.concatenate(parts, axis=0)


@jax.jit
def pq_decode(codes: Array, codebooks: Array) -> Array:
    """Reconstruct (N, Ds) float32 rows from (N, M) codes."""
    m = codebooks.shape[0]
    rows = codebooks[jnp.arange(m)[None, :], codes.astype(jnp.int32)]
    return rows.reshape(codes.shape[0], -1)


def pq_lut(q: Array, codebooks: Array, cent_sq: Optional[Array] = None) -> Array:
    """Per-query ADC lookup tables: (Q, M, C) rank-equivalent distances.

    ``lut[q, m, c] = ‖c‖² − 2·q_m·c`` — summing a row's M entries gives the
    rank-equivalent L2 score of the query against that row's
    *reconstruction* (`pq_decode`), exactly (see `pq_adc_scores`).
    """
    m, _, dsub = codebooks.shape
    if cent_sq is None:
        cent_sq = pq_cent_sq(codebooks)
    qs = q.astype(jnp.float32).reshape(q.shape[0], m, dsub)
    ip = jnp.einsum("qmd,mcd->qmc", qs, codebooks,
                    preferred_element_type=jnp.float32)
    return cent_sq[None, :, :] - 2.0 * ip


def pq_adc_scores(lut: Array, codes: Array) -> Array:
    """(Q, N) ADC scores: M table lookups per row, no decode.

    Identity: ``pq_adc_scores(pq_lut(q, cb), codes)`` equals
    ``l2_scores(q, pq_decode(codes, cb))`` up to f32 summation order — the
    property the codec tests pin.
    """
    m = codes.shape[1]
    idx = codes.astype(jnp.int32)
    acc = jnp.take(lut[:, 0, :], idx[:, 0], axis=1)
    for j in range(1, m):
        acc = acc + jnp.take(lut[:, j, :], idx[:, j], axis=1)
    return acc


def build_pq_index(
    db: Array,
    sched: ProgressiveSchedule,
    *,
    m: Optional[int] = None,
    n_codes: int = 256,
    n_iter: int = 10,
    train_rows: int = 65536,
    valid: Optional[Array] = None,
    seed: int = 0,
) -> Dict[str, Array]:
    """Stage-0 PQ code block + full-precision corpus + codebooks.

    Codebooks are fit on (a bounded sample of) live rows only; codes are
    emitted for every buffer row (static shape — dead/unpopulated slots are
    masked at search time).  An all-dead buffer degenerates to codebooks
    fit on zero rows, which is harmless: nothing is returnable anyway.
    """
    ds = sched.stages[0].dim
    m = m or auto_pq_m(ds)
    x = db[:, :ds]
    n = x.shape[0]
    if valid is not None:
        live = np.nonzero(np.asarray(valid[:n]))[0]
    else:
        live = np.arange(n)
    if live.size == 0:
        live = np.arange(min(n, 1))
    rng = np.random.default_rng(seed)
    if live.size > train_rows:
        live = np.sort(rng.choice(live, train_rows, replace=False))
    train = x[jnp.asarray(live)]
    codebooks = train_pq(train, m=m, n_codes=n_codes, n_iter=n_iter,
                         key=jax.random.PRNGKey(seed))
    codes = pq_encode(x, codebooks)
    return {
        "db": db,
        "codes": codes,                   # (N, M) uint8
        "codebooks": codebooks,           # (M, C, dsub) f32
        "cent_sq": pq_cent_sq(codebooks),  # (M, C) f32
    }


def _stage0_ids(codes, valid, row_limit):
    """(N,) int32 ids with every stage-0-unreturnable slot masked to -1."""
    n0 = codes.shape[0]
    ids = jnp.arange(n0, dtype=jnp.int32)
    keep = jnp.ones((n0,), bool)
    if valid is not None:
        keep = keep & valid[:n0]
    if row_limit is not None:
        keep = keep & (jnp.arange(n0) < row_limit)
    return jnp.where(keep, ids, -1)


def _finish(q, rescore_db, sched, scores, cand, *, valid, extra_cand,
            metric):
    """Shared post-stage-0 path: tail injection (``stage0/tail``) + the
    rescore ladder."""
    from repro.core.progressive import rescore_ladder
    from repro.core.quant import quant_rest_stages

    with jax.named_scope("stage0"), jax.named_scope("tail"):
        cand = T.inject_candidates(cand, extra_cand)
    return rescore_ladder(
        q, rescore_db, cand,
        quant_rest_stages(sched, extra_cand=extra_cand, valid=valid),
        valid=valid, metric=metric, scores=scores,
    )


@functools.partial(
    jax.jit, static_argnames=("sched", "metric", "oversample"))
def pq_progressive_search(
    q: Array, idx: Dict[str, Array], sched: ProgressiveSchedule,
    *, metric: str = "l2",
    db: Optional[Array] = None,
    valid: Optional[Array] = None,
    row_limit: Optional[Array] = None,
    extra_cand: Optional[Array] = None,
    oversample: int = 1,
) -> Tuple[Array, Array]:
    """Progressive search with a PQ ADC stage-0 scan (XLA reference).

    Stage 0 ranks every coded row by ADC lookup; every later stage rescores
    the survivors at full precision, so the final results carry exact
    distances.  ``oversample`` widens the stage-0 survivor pool to
    ``oversample × k0`` — the classic PQ remedy for ADC ranking noise
    (widening the cheap stage is nearly free; the full-precision rescore
    cuts the pool back).  The mutable-corpus extensions (``db``/``valid``/
    ``row_limit``/``extra_cand``) mean exactly what they mean for
    `repro.core.quant.quantized_progressive_search`.  Named scopes:
    ``stage0`` with ``lut`` (the ADC tables), ``scan`` (the id mask, ADC
    scan and top-k) and ``tail`` (the injected tail window); then
    ``rescore``.
    """
    if metric != "l2":
        raise ValueError(
            f"PQ ADC scores are rank-equivalent L2 distances; got "
            f"metric={metric!r}")
    s0 = sched.stages[0]
    rescore_db = idx["db"] if db is None else db
    codes = idx["codes"]
    n0 = codes.shape[0]
    ds = idx["codebooks"].shape[0] * idx["codebooks"].shape[2]
    with jax.named_scope("stage0"):
        with jax.named_scope("lut"):
            lut = pq_lut(q[:, :ds], idx["codebooks"], idx["cent_sq"])
        with jax.named_scope("scan"):
            scores = pq_adc_scores(lut, codes)
            ids = _stage0_ids(codes, valid, row_limit)
            scores = jnp.where(ids[None, :] >= 0, scores, jnp.inf)
            neg, cand = jax.lax.top_k(-scores, min(s0.k * oversample, n0))
            # fully-masked slots must surface the -1 sentinel, not row 0
            cand = jnp.where(jnp.isfinite(-neg), cand.astype(jnp.int32), -1)
    return _finish(q, rescore_db, sched, -neg, cand,
                   valid=valid, extra_cand=extra_cand, metric=metric)


@functools.partial(
    jax.jit,
    static_argnames=("sched", "metric", "block_m", "oversample",
                     "interpret"))
def pq_progressive_search_kernel(
    q: Array, idx: Dict[str, Array], sched: ProgressiveSchedule,
    *, metric: str = "l2",
    db: Optional[Array] = None,
    valid: Optional[Array] = None,
    row_limit: Optional[Array] = None,
    extra_cand: Optional[Array] = None,
    block_m: int = 128,
    oversample: int = 1,
    interpret: bool = False,
) -> Tuple[Array, Array]:
    """`pq_progressive_search` with the fused Pallas ADC stage-0 kernel.

    Same results (identical top-k id sets — the parity contract
    `tests/test_kernels.py` enforces), but stage 0 runs
    `repro.kernels.pq_scan.pq_scan_topk`: the per-query (M, C) LUT stays
    VMEM-resident while uint8 code slabs stream HBM→VMEM once and the
    running top-k never leaves VMEM.  Named scopes: ``stage0`` with ``lut``,
    ``scan`` (the id mask and the fused kernel) and ``tail``; then
    ``rescore``.
    """
    from repro.kernels.pq_scan import pq_scan_topk

    if metric != "l2":
        raise ValueError(
            f"PQ ADC scores are rank-equivalent L2 distances; got "
            f"metric={metric!r}")
    s0 = sched.stages[0]
    rescore_db = idx["db"] if db is None else db
    codes = idx["codes"]
    n0 = codes.shape[0]
    ds = idx["codebooks"].shape[0] * idx["codebooks"].shape[2]
    with jax.named_scope("stage0"):
        with jax.named_scope("lut"):
            lut = pq_lut(q[:, :ds], idx["codebooks"], idx["cent_sq"])
        with jax.named_scope("scan"):
            ids = _stage0_ids(codes, valid, row_limit)
            scores, cand = pq_scan_topk(
                lut, codes, ids, k=min(s0.k * oversample, n0),
                block_m=block_m, interpret=interpret)
    return _finish(q, rescore_db, sched, scores, cand,
                   valid=valid, extra_cand=extra_cand, metric=metric)
