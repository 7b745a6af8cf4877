"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles,
in interpret mode (`tests/test_tpu_compile.py` compiles the kernels for a
TPU v5e)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.distance_topk import l2_topk
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gather_rescore import gather_rescore
from repro.kernels.ivf_scan import ivf_scan_topk, pack_ivf_lists, update_pack
from repro.kernels import ref

RNG = np.random.default_rng(42)


def _random_ivf(n, n_lists, max_len, rng, *, coverage=1.0):
    """Random -1-padded member table over a subset of rows (no duplicates)."""
    lists = np.full((n_lists, max_len), -1, np.int32)
    rows = rng.permutation(n)[: int(n * coverage)]
    assign = rng.integers(0, n_lists, rows.size)
    for c in range(n_lists):
        mem = rows[assign == c][:max_len]
        lists[c, : mem.size] = mem
    return lists


def _id_sets(ids):
    return [set(int(x) for x in row if x >= 0) for row in np.asarray(ids)]


class TestDistanceTopK:
    @pytest.mark.parametrize("nq,n,d,k,bq,bn", [
        (16, 256, 32, 4, 8, 64),
        (100, 1000, 64, 8, 32, 128),     # uneven tiles
        (7, 130, 16, 3, 8, 64),          # heavy padding
        (32, 512, 128, 16, 32, 256),
    ])
    @pytest.mark.parametrize("norms", [False, True])
    def test_matches_ref(self, nq, n, d, k, bq, bn, norms):
        q = RNG.normal(size=(nq, d)).astype(np.float32)
        db = RNG.normal(size=(n, d)).astype(np.float32)
        sq = jnp.sum(jnp.asarray(db) ** 2, axis=-1) if norms else None
        s, i = l2_topk(jnp.asarray(q), jnp.asarray(db), k=k, db_sq=sq,
                       block_q=bq, block_n=bn, interpret=True)
        rs, ri = ref.l2_topk_ref(jnp.asarray(q), jnp.asarray(db), k)
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   rtol=1e-4, atol=1e-4)
        assert (np.asarray(i) == np.asarray(ri)).mean() > 0.99

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = jnp.asarray(RNG.normal(size=(16, 32)), dtype)
        db = jnp.asarray(RNG.normal(size=(128, 32)), dtype)
        s, i = l2_topk(q, db, k=4, block_q=8, block_n=64, interpret=True)
        rs, ri = ref.l2_topk_ref(q, db, 4)
        tol = 1e-4 if dtype == np.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   rtol=tol, atol=tol)

    def test_merge_breaks_ties_toward_lower_index(self):
        """The in-kernel merge orders exact score ties (duplicated db rows)
        by the lower db index, as ``lax.top_k`` does, also on a db size
        that is not a multiple of block_n."""
        d, k = 16, 6
        base = RNG.normal(size=(40, d)).astype(np.float32)
        db = np.concatenate([base, base[:13]])   # 53 rows: dup-row ties +
        q = base[:9] + 0.05 * RNG.normal(size=(9, d)).astype(np.float32)
        s, i = l2_topk(jnp.asarray(q), jnp.asarray(db), k=k,
                       block_q=8, block_n=16, interpret=True)
        sa, ia = np.asarray(s), np.asarray(i)
        assert (np.diff(sa, axis=1) >= 0).all()
        # each query's own row is duplicated at +40: both copies come back
        # first, and where their scores tie exactly the lower index leads
        for r in range(9):
            assert set(ia[r, :2]) == {r, r + 40}
            if sa[r, 0] == sa[r, 1]:
                assert ia[r, 0] == r
        rs, _ = ref.l2_topk_ref(jnp.asarray(q), jnp.asarray(db), k)
        np.testing.assert_allclose(sa, np.asarray(rs), rtol=1e-4, atol=1e-4)

    def test_precomputed_norms(self):
        q = jnp.asarray(RNG.normal(size=(8, 16)), jnp.float32)
        db = jnp.asarray(RNG.normal(size=(64, 16)), jnp.float32)
        sq = jnp.sum(db**2, axis=-1)
        s1, i1 = l2_topk(q, db, k=2, db_sq=sq, block_q=8, block_n=32,
                         interpret=True)
        s2, i2 = l2_topk(q, db, k=2, block_q=8, block_n=32, interpret=True)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


class TestIvfScan:
    """Fused IVF probe+scan kernel vs the jnp oracle and the XLA IVF path."""

    @pytest.mark.parametrize("n,d,n_lists,max_len,bm,nq,n_probe,k", [
        (300, 16, 8, 64, 16, 5, 3, 10),
        (250, 32, 6, 48, 16, 7, 4, 8),      # max_len not a block multiple
        (200, 8, 10, 13, 8, 4, 5, 6),       # heavy pad: 13 -> 16
        (120, 24, 4, 64, 64, 3, 2, 12),     # single chunk per list
    ])
    @pytest.mark.parametrize("tombstones", [0.0, 0.2])
    def test_matches_ref(self, n, d, n_lists, max_len, bm, nq, n_probe, k,
                         tombstones):
        rng = np.random.default_rng(n + max_len)
        db = rng.normal(size=(n, d)).astype(np.float32)
        lists = _random_ivf(n, n_lists, max_len, rng, coverage=0.9)
        probe = np.stack([rng.choice(n_lists, n_probe, replace=False)
                          for _ in range(nq)]).astype(np.int32)
        q = rng.normal(size=(nq, d)).astype(np.float32)
        pack = pack_ivf_lists(jnp.asarray(db), jnp.asarray(lists), dim=d,
                              block_m=bm)
        lists[rng.random(lists.shape) < tombstones] = -1
        s, i = ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                             jnp.asarray(lists), pack, k=k, interpret=True)
        rs, ri = ref.ivf_scan_ref(jnp.asarray(q), jnp.asarray(db),
                                  jnp.asarray(lists), jnp.asarray(probe),
                                  dim=d, k=k)
        assert _id_sets(i) == _id_sets(ri)
        ss = np.sort(np.asarray(s), axis=1)
        rr = np.sort(np.asarray(rs), axis=1)
        fin = np.isfinite(rr)
        np.testing.assert_allclose(ss[fin], rr[fin], rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.isinf(ss), np.isinf(rr))

    def test_tombstoned_and_empty_lists(self):
        """Masked ids never surface; a fully-masked probe set yields -1."""
        rng = np.random.default_rng(7)
        n, d, n_lists, max_len = 150, 16, 6, 32
        db = rng.normal(size=(n, d)).astype(np.float32)
        lists = _random_ivf(n, n_lists, max_len, rng)
        lists[2] = -1                                 # empty list
        valid = rng.random(n) > 0.3
        masked = np.where((lists >= 0) & valid[np.maximum(lists, 0)],
                          lists, -1).astype(np.int32)
        pack = pack_ivf_lists(jnp.asarray(db), jnp.asarray(lists), dim=d,
                              block_m=16)
        q = rng.normal(size=(4, d)).astype(np.float32)
        probe = np.stack([[0, 2, 4], [1, 2, 5], [2, 3, 0], [2, 2 + 3, 1]]
                         ).astype(np.int32)
        s, i = ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                             jnp.asarray(masked), pack, k=8, interpret=True)
        ia = np.asarray(i)
        live = ia[ia >= 0]
        assert valid[live].all()                      # no tombstone returned
        # and against the oracle over the masked table
        rs, ri = ref.ivf_scan_ref(jnp.asarray(q), jnp.asarray(db),
                                  jnp.asarray(masked), jnp.asarray(probe),
                                  dim=d, k=8)
        assert _id_sets(i) == _id_sets(ri)

    def test_k_exceeds_candidates(self):
        rng = np.random.default_rng(3)
        n, d = 40, 8
        db = rng.normal(size=(n, d)).astype(np.float32)
        lists = _random_ivf(n, 4, 8, rng, coverage=0.5)
        pack = pack_ivf_lists(jnp.asarray(db), jnp.asarray(lists), dim=d,
                              block_m=8)
        q = rng.normal(size=(2, d)).astype(np.float32)
        probe = np.asarray([[0, 1], [2, 3]], np.int32)
        s, i = ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                             jnp.asarray(lists), pack, k=30, interpret=True)
        sa, ia = np.asarray(s), np.asarray(i)
        assert (ia >= 0).sum(1).max() <= 16           # at most 2 lists x 8
        assert np.isinf(sa[ia < 0]).all()

    @pytest.mark.parametrize("with_valid", [False, True])
    @pytest.mark.parametrize("with_tail", [False, True])
    def test_parity_vs_xla_sched_path(self, with_valid, with_tail):
        """The acceptance contract: identical top-k id sets to
        `ivf_progressive_search_sched` under fixed probes/schedule, across
        validity masking and tail extra_cand injection."""
        from repro.core import make_schedule
        from repro.core.ivf import (build_ivf, ivf_progressive_search_kernel,
                                    ivf_progressive_search_sched)
        rng = np.random.default_rng(17)
        n, d = 400, 64
        db = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(9, d)).astype(np.float32))
        sched = make_schedule(8, d, 32, final_k=5)
        ivf = build_ivf(db, 12)
        valid = (jnp.asarray(rng.random(n) > 0.15) if with_valid else None)
        tail = (jnp.asarray(np.r_[np.arange(n - 8, n),
                                  -np.ones(5)].astype(np.int32))
                if with_tail else None)
        kw = dict(n_probe=5, valid=valid, extra_cand=tail)
        s1, i1 = ivf_progressive_search_sched(
            q, db, ivf["centroids"], ivf["lists"], sched, **kw)
        s2, i2 = ivf_progressive_search_kernel(
            q, db, ivf["centroids"], ivf["lists"], sched, interpret=True,
            **kw)
        assert _id_sets(i1) == _id_sets(i2)
        np.testing.assert_allclose(
            np.sort(np.asarray(s1), axis=1), np.sort(np.asarray(s2), axis=1),
            rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", ["float32", "int8", "pq"])
    @pytest.mark.parametrize("nq", [2, 4])   # Q·n_probe 10 < 12 lists < 20
    def test_parity_under_tombstones_and_filter_mask(self, dtype, nq):
        """The kernel program masks only the probed lists' slots (or, once
        Q·n_probe reaches n_lists, the whole member table): with tombstones
        and a filter-style mask inside the probed lists it answers exactly
        as the kernel fed a member table masked whole before the probe
        gather (the oracle), and, for f32 slabs, with the id sets of
        `ivf_progressive_search_sched`."""
        import jax
        from repro.core import make_schedule
        from repro.core import truncated as T
        from repro.core.ivf import (build_ivf, ivf_progressive_search_kernel,
                                    ivf_progressive_search_sched)
        from repro.core.pq import train_pq
        from repro.kernels.ivf_scan import probed_ids
        rng = np.random.default_rng(29)
        n, d, d0, n_lists, n_probe = 400, 64, 8, 12, 5
        db = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(nq, d)).astype(np.float32))
        sched = make_schedule(d0, d, 32, final_k=5)
        ivf = build_ivf(db, n_lists)
        lists = np.asarray(ivf["lists"])
        _, probe = jax.lax.top_k(-T.l2_scores(q, ivf["centroids"]), n_probe)
        probe = np.asarray(probe)
        tombstone = rng.random(n) < 0.15
        filtered = np.arange(n) % 3 == 0             # a tenant's bitmask
        valid = ~tombstone & ~filtered
        probed = lists[probe]
        probed = probed[probed >= 0]
        assert tombstone[probed].any() and filtered[probed].any()
        masked = np.where((lists >= 0) & valid[np.maximum(lists, 0)],
                          lists, -1).astype(np.int32)
        # either order of mask and gather gives the same per-query table
        np.testing.assert_array_equal(
            np.asarray(probed_ids(jnp.asarray(lists), jnp.asarray(probe),
                                  jnp.asarray(valid))),
            masked[probe])

        cb = (train_pq(db[:, :d0], m=4, n_codes=32, n_iter=4)
              if dtype == "pq" else None)
        pack = pack_ivf_lists(db, ivf["lists"], dim=d0, dtype=dtype,
                              block_m=16, pq_codebooks=cb)
        kw = dict(n_probe=n_probe, pack=pack, interpret=True,
                  pq_oversample=2 if dtype == "pq" else 1)
        s, i = ivf_progressive_search_kernel(
            q, db, ivf["centroids"], ivf["lists"], sched,
            valid=jnp.asarray(valid), **kw)
        so, io = ivf_progressive_search_kernel(
            q, db, ivf["centroids"], jnp.asarray(masked), sched, **kw)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(io))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(so))
        ia = np.asarray(i)
        assert valid[ia[ia >= 0]].all()
        if dtype == "float32":
            _, ix = ivf_progressive_search_sched(
                q, db, ivf["centroids"], ivf["lists"], sched,
                n_probe=n_probe, valid=jnp.asarray(valid))
            assert _id_sets(i) == _id_sets(ix)

    def test_int8_pack_composes(self):
        """int8 member slabs: valid results, near-f32 ranking quality."""
        rng = np.random.default_rng(23)
        n, d, n_lists, max_len = 400, 32, 8, 64
        db = rng.normal(size=(n, d)).astype(np.float32)
        lists = _random_ivf(n, n_lists, max_len, rng)
        q = rng.normal(size=(16, d)).astype(np.float32)
        probe = np.stack([rng.choice(n_lists, 4, replace=False)
                          for _ in range(16)]).astype(np.int32)
        pf = pack_ivf_lists(jnp.asarray(db), jnp.asarray(lists), dim=d,
                            block_m=16)
        p8 = pack_ivf_lists(jnp.asarray(db), jnp.asarray(lists), dim=d,
                            block_m=16, dtype="int8")
        assert p8["rows"].dtype == jnp.int8
        _, i_f = ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                               jnp.asarray(lists), pf, k=10, interpret=True)
        _, i_8 = ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                               jnp.asarray(lists), p8, k=10, interpret=True)
        overlap = np.mean([
            len(a & b) / max(len(a), 1)
            for a, b in zip(_id_sets(i_f), _id_sets(i_8))])
        assert overlap >= 0.8                   # int8 is stage-0 only; the
        # full-precision rescore ladder absorbs the residual ranking noise

    @pytest.mark.parametrize("dtype", ["float32", "int8"])
    def test_update_pack_absorbs_new_rows(self, dtype):
        """Incremental append: a row written into a spare slot scores like
        a built one (int8 codes reuse the stored scale)."""
        rng = np.random.default_rng(5)
        n, d, n_lists, max_len = 100, 16, 4, 32
        db = rng.normal(size=(n + 1, d)).astype(np.float32)
        lists = _random_ivf(n, n_lists, max_len, rng, coverage=0.5)
        pack = pack_ivf_lists(jnp.asarray(db[:n]), jnp.asarray(lists), dim=d,
                              block_m=16, dtype=dtype)
        # place the new row (id n) into list 1's first free slot
        slot = int((lists[1] >= 0).sum())
        lists[1, slot] = n
        pack = update_pack(pack, jnp.asarray(db), np.asarray([n], np.int32),
                           np.asarray([1 * pack["max_len"] + slot]))
        q = db[n:n + 1] + 0.01 * rng.normal(size=(1, d)).astype(np.float32)
        probe = np.asarray([[1, 0]], np.int32)
        _, i = ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                             jnp.asarray(lists), pack, k=1, interpret=True)
        assert int(np.asarray(i)[0, 0]) == n

    def test_bytes_model_fused_strictly_fewer(self):
        from repro.kernels.ivf_scan import stage0_bytes_model
        for d0 in (1, 4, 8, 64, 256):
            for mb in (4, 1):
                m = stage0_bytes_model(n_lists=64, max_len=128, n_probe=8,
                                       d0=d0, k=32, member_bytes=mb)
                assert m["fused_bytes"] < m["xla_bytes"]


class TestPqScan:
    """Fused PQ ADC LUT-scan kernel (flat + IVF-slab variants) vs the jnp
    ADC oracles and the XLA `pq_progressive_search` path."""

    @staticmethod
    def _codec(db, d, m, rng, n_codes=64):
        from repro.core.pq import pq_lut, train_pq
        cb = train_pq(jnp.asarray(db[:, :d]), m=m, n_codes=n_codes, n_iter=6)

        def lut_of(q):
            return pq_lut(jnp.asarray(q[:, :d]), cb)

        return cb, lut_of

    @pytest.mark.parametrize("n,d,m,bm,nq,k", [
        (300, 16, 4, 32, 5, 10),
        (250, 32, 8, 64, 7, 8),        # n not a block multiple
        (130, 8, 2, 128, 3, 6),        # single chunk, heavy pad
        (200, 24, 3, 16, 4, 12),       # odd subspace count
    ])
    @pytest.mark.parametrize("tombstones", [0.0, 0.2])
    def test_flat_matches_ref(self, n, d, m, bm, nq, k, tombstones):
        from repro.core.pq import pq_encode
        from repro.kernels.pq_scan import pq_scan_topk
        rng = np.random.default_rng(n + m)
        db = rng.normal(size=(n, d)).astype(np.float32)
        q = rng.normal(size=(nq, d)).astype(np.float32)
        cb, lut_of = self._codec(db, d, m, rng)
        codes = pq_encode(jnp.asarray(db[:, :d]), cb)
        ids = np.arange(n, dtype=np.int32)
        ids[rng.random(n) < tombstones] = -1
        lut = lut_of(q)
        s, i = pq_scan_topk(lut, codes, jnp.asarray(ids), k=k, block_m=bm,
                            interpret=True)
        rs, ri = ref.pq_scan_ref(lut, codes, jnp.asarray(ids), k=k)
        assert _id_sets(i) == _id_sets(ri)
        ss, rr = np.sort(np.asarray(s), 1), np.sort(np.asarray(rs), 1)
        fin = np.isfinite(rr)
        np.testing.assert_allclose(ss[fin], rr[fin], rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.isinf(ss), np.isinf(rr))
        # no tombstone ever surfaces
        live = np.asarray(i)[np.asarray(i) >= 0]
        assert (ids[live] >= 0).all()

    @pytest.mark.parametrize("tombstones", [0.0, 0.2])
    def test_ivf_slab_matches_ref(self, tombstones):
        from repro.core.pq import pq_encode
        from repro.kernels.ivf_scan import pack_ivf_lists
        from repro.kernels.pq_scan import pq_ivf_scan_topk
        rng = np.random.default_rng(31)
        n, d, m, n_lists, max_len = 400, 32, 4, 8, 48   # 48 -> pads to 64
        db = rng.normal(size=(n, d)).astype(np.float32)
        q = rng.normal(size=(9, d)).astype(np.float32)
        lists = _random_ivf(n, n_lists, max_len, rng, coverage=0.9)
        cb, lut_of = self._codec(db, d, m, rng)
        pack = pack_ivf_lists(jnp.asarray(db), jnp.asarray(lists), dim=d,
                              dtype="pq", pq_codebooks=cb, block_m=16)
        assert pack["rows"].dtype == jnp.uint8
        assert pack["sq"] is None                 # ADC needs no norm table
        probe = np.stack([rng.choice(n_lists, 4, replace=False)
                          for _ in range(9)]).astype(np.int32)
        lists[rng.random(lists.shape) < tombstones] = -1
        s, i = pq_ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                                jnp.asarray(lists), pack, k=10,
                                interpret=True)
        codes_full = pq_encode(jnp.asarray(db[:, :d]), cb)
        rs, ri = ref.pq_ivf_scan_ref(lut_of(q), codes_full,
                                     jnp.asarray(lists), jnp.asarray(probe),
                                     k=10)
        assert _id_sets(i) == _id_sets(ri)
        ss, rr = np.sort(np.asarray(s), 1), np.sort(np.asarray(rs), 1)
        fin = np.isfinite(rr)
        np.testing.assert_allclose(ss[fin], rr[fin], rtol=1e-4, atol=1e-4)

    def test_ivf_slab_tombstones_and_empty_lists(self):
        """Masked ids never surface; a fully-masked probe set yields -1."""
        from repro.core.pq import pq_encode
        from repro.kernels.ivf_scan import pack_ivf_lists
        from repro.kernels.pq_scan import pq_ivf_scan_topk
        rng = np.random.default_rng(13)
        n, d, m, n_lists, max_len = 150, 16, 4, 6, 32
        db = rng.normal(size=(n, d)).astype(np.float32)
        lists = _random_ivf(n, n_lists, max_len, rng)
        lists[2] = -1                                 # empty list
        valid = rng.random(n) > 0.3
        masked = np.where((lists >= 0) & valid[np.maximum(lists, 0)],
                          lists, -1).astype(np.int32)
        cb, lut_of = self._codec(db, d, m, rng)
        pack = pack_ivf_lists(jnp.asarray(db), jnp.asarray(lists), dim=d,
                              dtype="pq", pq_codebooks=cb, block_m=16)
        q = rng.normal(size=(4, d)).astype(np.float32)
        probe = np.asarray([[0, 2, 4], [1, 2, 5], [2, 3, 0], [2, 5, 1]],
                           np.int32)
        s, i = pq_ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                                jnp.asarray(masked), pack, k=8,
                                interpret=True)
        ia = np.asarray(i)
        live = ia[ia >= 0]
        assert valid[live].all()                      # no tombstone returned
        codes_full = pq_encode(jnp.asarray(db[:, :d]), cb)
        rs, ri = ref.pq_ivf_scan_ref(lut_of(q), codes_full,
                                     jnp.asarray(masked), jnp.asarray(probe),
                                     k=8)
        assert _id_sets(i) == _id_sets(ri)

    @pytest.mark.parametrize("with_valid", [False, True])
    @pytest.mark.parametrize("with_tail", [False, True])
    def test_parity_vs_xla_adc_path(self, with_valid, with_tail):
        """The acceptance contract: the fused flat kernel path produces
        identical top-k id sets to the XLA ADC reference, across validity
        masking and tail extra_cand injection."""
        from repro.core import make_schedule
        from repro.core.pq import (build_pq_index, pq_progressive_search,
                                   pq_progressive_search_kernel)
        rng = np.random.default_rng(19)
        n, d = 400, 64
        db = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(9, d)).astype(np.float32))
        sched = make_schedule(16, d, 32, final_k=5)
        idx = build_pq_index(db, sched, m=4)
        valid = (jnp.asarray(rng.random(n) > 0.15) if with_valid else None)
        tail = (jnp.asarray(np.r_[np.arange(n - 8, n),
                                  -np.ones(5)].astype(np.int32))
                if with_tail else None)
        kw = dict(valid=valid, extra_cand=tail, oversample=2)
        s1, i1 = pq_progressive_search(q, idx, sched, **kw)
        s2, i2 = pq_progressive_search_kernel(q, idx, sched, interpret=True,
                                              block_m=64, **kw)
        assert _id_sets(i1) == _id_sets(i2)
        np.testing.assert_allclose(
            np.sort(np.asarray(s1), axis=1), np.sort(np.asarray(s2), axis=1),
            rtol=1e-4, atol=1e-4)

    def test_ivf_pq_search_end_to_end(self):
        """`ivf_progressive_search_kernel` over a pq pack: against the
        exact-over-probed-members baseline, ADC stage 0 with the default
        oversample loses nothing vs the f32 stage 0 — the full-precision
        rescore ladder absorbs the quantization noise."""
        import jax
        from repro.core import make_schedule
        from repro.core import truncated as T
        from repro.core.ivf import (build_ivf, ivf_progressive_search_kernel,
                                    ivf_progressive_search_sched)
        from repro.kernels.ivf_scan import pack_ivf_lists
        rng = np.random.default_rng(23)
        n, d = 400, 64
        db = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        q = jnp.asarray(rng.normal(size=(16, d)).astype(np.float32))
        sched = make_schedule(16, d, 32, final_k=10)
        ivf = build_ivf(db, 12)
        # backend-default codec quality: 256 codes/subspace, 4x oversample
        cb, _ = self._codec(np.asarray(db), 16, 4, rng, n_codes=256)
        pack = pack_ivf_lists(db, ivf["lists"], dim=16, dtype="pq",
                              pq_codebooks=cb, block_m=16)
        _, i_pq = ivf_progressive_search_kernel(
            q, db, ivf["centroids"], ivf["lists"], sched, n_probe=6,
            pack=pack, pq_oversample=4, interpret=True)
        _, i_f = ivf_progressive_search_sched(
            q, db, ivf["centroids"], ivf["lists"], sched, n_probe=6)
        # exact top-10 over the same probed members at the full dim
        cs = T.l2_scores(q, ivf["centroids"])
        _, probe = jax.lax.top_k(-cs, 6)
        _, i_exact = ref.ivf_scan_ref(q, db, ivf["lists"], probe, dim=d,
                                      k=10)
        def recall(i):
            return np.mean([
                len(a & b) / max(len(b), 1)
                for a, b in zip(_id_sets(i), _id_sets(i_exact))])
        # both paths pay the same truncated-stage-0 noise; PQ must not pay
        # meaningfully more on top of it
        assert recall(i_pq) >= recall(i_f) - 0.05

    def test_oversampled_pool_seats_tail_rows(self):
        """Tail (un-absorbed appended) rows must be able to claim any slot
        of the oversampled stage-0 pool, not just the first s0.k: a tail
        row with a mediocre stage-0 prefix but a perfect full-dim match
        must beat stage-0-flattering decoys at the rescore."""
        from repro.core import make_schedule
        from repro.core.ivf import build_ivf, ivf_progressive_search_kernel
        from repro.core.pq import train_pq
        from repro.kernels.ivf_scan import pack_ivf_lists
        rng = np.random.default_rng(41)
        d, n_coded = 16, 80
        q = rng.normal(size=(1, d)).astype(np.float32)
        coded = (rng.normal(size=(n_coded, d)) * 8 + 20).astype(np.float32)
        # 4 decoys: perfect stage-0 prefix, terrible suffix; 4 true
        # matches: slightly-off prefix, perfect suffix
        decoys = np.concatenate(
            [np.repeat(q[:, :8], 4, axis=0),
             np.full((4, 8), 30.0, np.float32)], axis=1)
        true = np.repeat(q, 4, axis=0) + np.concatenate(
            [np.full((4, 8), 0.5, np.float32), np.zeros((4, 8), np.float32)],
            axis=1).astype(np.float32)
        db = jnp.asarray(np.concatenate([coded, decoys, true]))
        tail_ids = np.arange(n_coded, n_coded + 8, dtype=np.int32)
        sched = make_schedule(8, d, 4, final_k=4)
        ivf = build_ivf(db[:n_coded], 4)
        cb = train_pq(db[:n_coded, :8], m=2, n_codes=32, n_iter=4)
        pack = pack_ivf_lists(db, ivf["lists"], dim=8, dtype="pq",
                              pq_codebooks=cb, block_m=16)
        _, ids = ivf_progressive_search_kernel(
            jnp.asarray(q), db, ivf["centroids"], ivf["lists"], sched,
            n_probe=2, pack=pack, pq_oversample=4,
            extra_cand=jnp.asarray(tail_ids), interpret=True)
        # the 4 true matches fill the final top-4; every decoy loses
        assert set(np.asarray(ids)[0].tolist()) == set(
            range(n_coded + 4, n_coded + 8))

    def test_update_pack_absorbs_new_rows_pq(self):
        """Incremental append: a row written into a spare slot is encoded
        against the pack's frozen codebooks and scores like a built one."""
        from repro.kernels.ivf_scan import pack_ivf_lists, update_pack
        from repro.kernels.pq_scan import pq_ivf_scan_topk
        rng = np.random.default_rng(5)
        n, d, m, n_lists, max_len = 100, 16, 4, 4, 32
        db = rng.normal(size=(n + 1, d)).astype(np.float32)
        lists = _random_ivf(n, n_lists, max_len, rng, coverage=0.5)
        cb, _ = self._codec(db[:n], d, m, rng)
        pack = pack_ivf_lists(jnp.asarray(db[:n]), jnp.asarray(lists), dim=d,
                              dtype="pq", pq_codebooks=cb, block_m=16)
        slot = int((lists[1] >= 0).sum())
        lists[1, slot] = n
        pack = update_pack(pack, jnp.asarray(db), np.asarray([n], np.int32),
                           np.asarray([1 * pack["max_len"] + slot]))
        q = db[n:n + 1] + 0.01 * rng.normal(size=(1, d)).astype(np.float32)
        probe = np.asarray([[1, 0]], np.int32)
        _, i = pq_ivf_scan_topk(jnp.asarray(q), jnp.asarray(probe),
                                jnp.asarray(lists), pack, k=1,
                                interpret=True)
        assert int(np.asarray(i)[0, 0]) == n

    def test_pack_rejects_wrong_scanner(self):
        from repro.core.pq import train_pq
        from repro.kernels.ivf_scan import ivf_scan_topk, pack_ivf_lists
        from repro.kernels.pq_scan import pq_ivf_scan_topk
        rng = np.random.default_rng(2)
        db = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
        lists = jnp.asarray(_random_ivf(64, 4, 16, rng))
        cb = train_pq(db, m=4, n_codes=16, n_iter=2)
        pq_pack = pack_ivf_lists(db, lists, dim=16, dtype="pq",
                                 pq_codebooks=cb)
        f_pack = pack_ivf_lists(db, lists, dim=16)
        q = jnp.zeros((1, 16), jnp.float32)
        probe = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="pq_scan"):
            ivf_scan_topk(q, probe, lists, pq_pack, k=4, interpret=True)
        with pytest.raises(ValueError, match="dtype='pq'"):
            pq_ivf_scan_topk(q, probe, lists, f_pack, k=4, interpret=True)
        with pytest.raises(ValueError, match="pq_codebooks"):
            pack_ivf_lists(db, lists, dim=16, dtype="pq")

    def test_flat_bytes_model_pq_strictly_under_int8(self):
        from repro.kernels.pq_scan import flat_stage0_bytes_model
        for d0, m in ((8, 1), (16, 2), (64, 8), (256, 32)):
            i8 = flat_stage0_bytes_model(n=65536, k=256, row_bytes=d0)
            pq = flat_stage0_bytes_model(n=65536, k=256, row_bytes=m,
                                         lut_bytes=m * 256 * 4)
            for key in ("xla_bytes", "fused_bytes"):
                assert pq[key] < i8[key]
            assert pq["fused_bytes"] < pq["xla_bytes"] + 8 * 256

    def test_ivf_bytes_model_pq_strictly_under_int8(self):
        from repro.kernels.ivf_scan import stage0_bytes_model
        for d0, m in ((16, 2), (64, 8), (256, 32)):
            i8 = stage0_bytes_model(n_lists=64, max_len=128, n_probe=8,
                                    d0=d0, k=32, member_bytes=1)
            pq = stage0_bytes_model(n_lists=64, max_len=128, n_probe=8,
                                    d0=d0, k=32, row_bytes=m,
                                    lut_bytes=m * 256 * 4, norms=False)
            assert pq["fused_bytes"] < i8["fused_bytes"]
            assert pq["fused_bytes"] < pq["xla_bytes"]


class TestGatherRescore:
    @pytest.mark.parametrize("nq,n,d,c,bc", [
        (8, 200, 64, 16, 8),
        (12, 500, 128, 20, 16),          # c not divisible by bc
        (4, 100, 256, 7, 4),
    ])
    def test_matches_ref(self, nq, n, d, c, bc):
        q = RNG.normal(size=(nq, d)).astype(np.float32)
        db = RNG.normal(size=(n, d)).astype(np.float32)
        cand = RNG.choice(n, size=(nq, c)).astype(np.int32)
        cand[0, c // 2:] = -1
        s = gather_rescore(jnp.asarray(q), jnp.asarray(db),
                           jnp.asarray(cand), block_c=bc, interpret=True)
        r = ref.gather_rescore_ref(jnp.asarray(q), jnp.asarray(db),
                                   jnp.asarray(cand))
        sa, ra = np.asarray(s), np.asarray(r)
        fin = np.isfinite(ra)
        np.testing.assert_allclose(sa[fin], ra[fin], rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(np.isinf(sa), np.isinf(ra))


class TestEmbeddingBag:
    @pytest.mark.parametrize("v,d,b,l,bb", [
        (100, 32, 16, 4, 8),
        (500, 64, 10, 7, 4),             # b not divisible by bb
        (50, 128, 4, 1, 2),
    ])
    @pytest.mark.parametrize("mode", ["sum", "mean"])
    def test_matches_ref(self, v, d, b, l, bb, mode):
        table = RNG.normal(size=(v, d)).astype(np.float32)
        idx = RNG.choice(v, size=(b, l)).astype(np.int32)
        idx[-1, l // 2:] = -1
        out = embedding_bag(jnp.asarray(table), jnp.asarray(idx),
                            mode=mode, block_b=bb, interpret=True)
        r = ref.embedding_bag_ref(jnp.asarray(table), jnp.asarray(idx),
                                  mode=mode)
        np.testing.assert_allclose(np.asarray(out), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("b,hq,hkv,sq,skv,dh,causal,window", [
        (2, 4, 4, 64, 64, 32, True, None),
        (2, 4, 2, 64, 64, 32, False, None),     # GQA
        (1, 2, 2, 50, 70, 32, True, None),      # uneven + decode-aligned
        (1, 2, 2, 96, 96, 64, True, 16),        # sliding window
        (1, 4, 1, 1, 128, 64, False, None),     # single-token decode (MQA)
        (1, 2, 2, 33, 65, 16, True, 8),         # padding both axes + window
    ])
    def test_matches_ref(self, b, hq, hkv, sq, skv, dh, causal, window):
        q = RNG.normal(size=(b, hq, sq, dh)).astype(np.float32)
        k = RNG.normal(size=(b, hkv, skv, dh)).astype(np.float32)
        v = RNG.normal(size=(b, hkv, skv, dh)).astype(np.float32)
        o = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=causal, window=window,
                            block_q=32, block_k=32, interpret=True)
        r = ref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), causal=causal,
                                    window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16(self):
        q = jnp.asarray(RNG.normal(size=(1, 2, 32, 32)), jnp.bfloat16)
        k = jnp.asarray(RNG.normal(size=(1, 2, 32, 32)), jnp.bfloat16)
        v = jnp.asarray(RNG.normal(size=(1, 2, 32, 32)), jnp.bfloat16)
        o = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                            interpret=True)
        r = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=5e-2, atol=5e-2)


class TestSegmentSum:
    @pytest.mark.parametrize("e,n,d,bn,ec", [
        (1000, 256, 32, 128, 256),
        (500, 128, 64, 64, 128),
        (2000, 384, 16, 128, 64),       # many chunks per block
        (50, 128, 8, 128, 32),          # sparse: most blocks empty
    ])
    def test_matches_ref(self, e, n, d, bn, ec):
        from repro.kernels.ops import segment_sum_op
        data = RNG.normal(size=(e, d)).astype(np.float32)
        seg = RNG.integers(0, n, e).astype(np.int32)
        seg[: e // 20] = -1             # padded edges
        out = segment_sum_op(jnp.asarray(data), jnp.asarray(seg),
                             num_segments=n, block_n=bn, edge_chunk=ec)
        masked = jnp.where((jnp.asarray(seg) >= 0)[:, None],
                           jnp.asarray(data), 0)
        expect = ref.segment_sum_ref(masked, jnp.maximum(jnp.asarray(seg), 0), n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)

    def test_skewed_degree_distribution(self):
        """Power-law receivers: one node takes most edges."""
        from repro.kernels.ops import segment_sum_op
        e, n, d = 800, 128, 16
        data = RNG.normal(size=(e, d)).astype(np.float32)
        seg = np.zeros(e, np.int32)
        seg[: e // 2] = 0               # half the edges hit node 0
        seg[e // 2:] = RNG.integers(0, n, e - e // 2)
        out = segment_sum_op(jnp.asarray(data), jnp.asarray(seg),
                             num_segments=n, block_n=64, edge_chunk=64)
        expect = ref.segment_sum_ref(jnp.asarray(data), jnp.asarray(seg), n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)


class TestChunkedAttentionParity:
    """The model's jnp chunked attention must match the Pallas kernel —
    they are the same math on different substrates."""

    def test_chunked_equals_flash(self):
        from repro.layers.attention import chunked_attention
        q = jnp.asarray(RNG.normal(size=(2, 4, 64, 32)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(2, 2, 64, 32)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(2, 2, 64, 32)), jnp.float32)
        a = chunked_attention(q, k, v, causal=True, window=0,
                              block_q=16, block_k=16)
        b = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

    def test_chunked_window_matches_ref(self):
        from repro.layers.attention import chunked_attention
        q = jnp.asarray(RNG.normal(size=(1, 2, 48, 16)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, 2, 48, 16)), jnp.float32)
        v = jnp.asarray(RNG.normal(size=(1, 2, 48, 16)), jnp.float32)
        a = chunked_attention(q, k, v, causal=True, window=jnp.asarray(8),
                              block_q=16, block_k=16)
        r = ref.flash_attention_ref(q, k, v, causal=True, window=8)
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)
