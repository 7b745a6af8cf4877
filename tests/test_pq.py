"""PQ codec unit tests: encode/decode/ADC identities, the quality knobs,
and the quantized backend's churn-aware (frozen-grid) maintenance."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import make_schedule
from repro.core.pq import (
    auto_pq_m,
    build_pq_index,
    pq_adc_scores,
    pq_decode,
    pq_encode,
    pq_lut,
    pq_progressive_search,
    train_pq,
)
from repro.core.truncated import l2_scores

RNG = np.random.default_rng(77)


def _db(n=300, d=32):
    return jnp.asarray(RNG.normal(size=(n, d)).astype(np.float32))


class TestCodec:
    def test_shapes_and_dtypes(self):
        db = _db()
        cb = train_pq(db, m=4, n_codes=32, n_iter=4)
        assert cb.shape == (4, 32, 8) and cb.dtype == jnp.float32
        codes = pq_encode(db, cb)
        assert codes.shape == (300, 4) and codes.dtype == jnp.uint8
        assert pq_decode(codes, cb).shape == db.shape

    def test_adc_equals_l2_to_reconstruction(self):
        """The ADC identity: summing a row's M LUT entries IS the
        rank-equivalent L2 score of the query vs that row's decode."""
        db = _db()
        q = jnp.asarray(RNG.normal(size=(7, 32)).astype(np.float32))
        cb = train_pq(db, m=8, n_codes=64, n_iter=6)
        codes = pq_encode(db, cb)
        adc = pq_adc_scores(pq_lut(q, cb), codes)
        exact = l2_scores(q, pq_decode(codes, cb))
        np.testing.assert_allclose(np.asarray(adc), np.asarray(exact),
                                   rtol=1e-4, atol=1e-3)

    def test_encode_is_optimal_assignment(self):
        """Reconstruction error is bounded by the codebook quantization
        error: no other code assignment reconstructs a row better."""
        db = _db(n=64)
        cb = train_pq(db, m=4, n_codes=16, n_iter=6)
        codes = np.asarray(pq_encode(db, cb))
        best = np.sum((np.asarray(pq_decode(jnp.asarray(codes), cb))
                       - np.asarray(db)) ** 2, axis=1)
        rng = np.random.default_rng(3)
        for _ in range(5):
            other = rng.integers(0, 16, codes.shape).astype(np.uint8)
            err = np.sum((np.asarray(pq_decode(jnp.asarray(other), cb))
                          - np.asarray(db)) ** 2, axis=1)
            assert (best <= err + 1e-4).all()

    def test_more_subspaces_reconstruct_better(self):
        db = _db(n=512, d=32)
        errs = []
        for m in (1, 4, 8):
            cb = train_pq(db, m=m, n_codes=64, n_iter=8)
            rec = pq_decode(pq_encode(db, cb), cb)
            errs.append(float(jnp.mean(jnp.sum((db - rec) ** 2, axis=1))))
        assert errs[0] > errs[1] > errs[2]

    def test_small_corpus_near_exact(self):
        """More codes than rows: k-means degenerates to ~one centroid per
        row and reconstruction is near-exact."""
        db = _db(n=100)
        cb = train_pq(db, m=4, n_codes=256, n_iter=8)
        rec = pq_decode(pq_encode(db, cb), cb)
        rel = (float(jnp.sum((db - rec) ** 2))
               / float(jnp.sum(db ** 2)))
        assert rel < 0.05

    def test_auto_m(self):
        assert auto_pq_m(128) == 32     # the paper's stage-0 width
        assert auto_pq_m(64) == 16
        assert auto_pq_m(12) == 3
        assert auto_pq_m(8) == 2
        assert auto_pq_m(4) == 1        # dsub stays >= 4 when small
        assert auto_pq_m(10) == 1       # indivisible: single subspace

    def test_indivisible_m_raises(self):
        with pytest.raises(ValueError, match="not divisible"):
            train_pq(_db(d=32), m=5)

    def test_too_many_codes_raises(self):
        with pytest.raises(ValueError, match="uint8"):
            train_pq(_db(), m=4, n_codes=512)


class TestPqProgressiveSearch:
    def test_self_retrieval_and_exact_final_scores(self):
        db = _db(n=200, d=32)
        sched = make_schedule(8, 32, 16, final_k=3)
        idx = build_pq_index(db, sched, m=2)
        s, i = pq_progressive_search(db[:6], idx, sched)
        np.testing.assert_array_equal(np.asarray(i)[:, 0], np.arange(6))
        # the final stage rescored at full precision: score of the hit is
        # the exact rank-equivalent self-distance -||x||^2
        expect = -np.sum(np.asarray(db[:6]) ** 2, axis=1)
        np.testing.assert_allclose(np.asarray(s)[:, 0], expect,
                                   rtol=1e-4, atol=1e-3)

    def test_oversample_recovers_adc_misses(self):
        """Widening the stage-0 pool improves recall vs exact search on the
        clustered workload (the knob the acceptance run leans on)."""
        from repro.core import truncated_search
        from repro.rag import make_clustered_corpus
        c = make_clustered_corpus(n_docs=2048, dim=64, n_queries=32,
                                  n_clusters=24, seed=5)
        db = jnp.asarray(c.db)
        q = jnp.asarray(c.queries)
        _, exact = truncated_search(q, db, dim=64, k=5, block_n=2048)
        sched = make_schedule(16, 64, 32, final_k=5)
        idx = build_pq_index(db, sched, m=4, n_codes=64)

        def recall(oversample):
            _, i = pq_progressive_search(q, idx, sched,
                                         oversample=oversample)
            return np.mean([
                len(set(map(int, a)) & set(map(int, b))) / 5
                for a, b in zip(np.asarray(i), np.asarray(exact))])

        r1, r8 = recall(1), recall(8)
        assert r8 >= r1
        assert r8 >= 0.9

    def test_metric_guard(self):
        db = _db(n=64)
        sched = make_schedule(8, 32, 16)
        idx = build_pq_index(db, sched, m=2)
        with pytest.raises(ValueError, match="rank-equivalent"):
            pq_progressive_search(db[:2], idx, sched, metric="cosine")


class TestQuantizedBackendCodecs:
    def _engine(self, codec, n_docs=200, **opts):
        from repro.engine import RetrievalEngine
        eng = RetrievalEngine(
            32, d_start=8, k0=16, buckets=(4,), capacity=64, block_n=64,
            backend="quantized",
            backend_opts={"codec": codec, "min_rebuild_rows": 16, **opts})
        db = np.random.default_rng(9).normal(
            size=(n_docs, 32)).astype(np.float32)
        eng.add_docs(db)
        return eng, db

    def test_bad_codec_rejected(self):
        with pytest.raises(ValueError, match="codec"):
            self._engine("fp4")

    def test_int8_kernel_flag_rejected(self):
        with pytest.raises(ValueError, match="codec='pq'"):
            self._engine("int8", use_kernel=True)

    def test_pq_m_must_divide(self):
        with pytest.raises(ValueError, match="does not divide"):
            self._engine("pq", pq_m=3)

    @pytest.mark.parametrize("codec", ["int8", "pq"])
    def test_appends_encoded_against_frozen_grid(self, codec):
        """Churn-aware maintenance: appended rows are encoded in place at
        safe points (coded_upto advances, the tail stays empty) and no
        rebuild fires below the churn threshold."""
        eng, db = self._engine(codec)
        eng.search(db[:1])                          # build
        state = eng.index_state
        n_rebuilds = eng.stats.n_rebuilds
        upto0 = state.data["coded_upto"]
        new = np.random.default_rng(1).normal(size=(8, 32)).astype(np.float32)
        ids = eng.add_docs(new)
        _, got = eng.search(new)                    # safe point absorbs
        np.testing.assert_array_equal(got[:, 0], ids)
        assert eng.index_state is state             # same state, mutated
        assert state.data["coded_upto"] == upto0 + 8
        assert eng.stats.n_rebuilds == n_rebuilds
        # absorbed rows rank at stage 0, not via the tail window
        assert eng.backend._tail_load(state, eng.store.stats()) == 0

    def test_encode_appends_off_rides_tail(self):
        eng, db = self._engine("pq", encode_appends=False)
        eng.search(db[:1])
        state = eng.index_state
        upto0 = state.data["coded_upto"]
        new = np.random.default_rng(2).normal(size=(4, 32)).astype(np.float32)
        ids = eng.add_docs(new)
        _, got = eng.search(new)                    # reachable via tail
        np.testing.assert_array_equal(got[:, 0], ids)
        assert state.data["coded_upto"] == upto0
        assert eng.backend._tail_load(state, eng.store.stats()) == 4

    def test_appends_past_block_capacity_ride_tail(self):
        """The code block is capacity-shaped: rows landing beyond it (the
        store grew) stay reachable through the tail window."""
        eng, db = self._engine("pq", n_docs=250)    # capacity grew to 256
        eng.search(db[:1])
        state = eng.index_state
        assert state.data["n_coded"] == 256
        new = np.random.default_rng(3).normal(
            size=(10, 32)).astype(np.float32)       # rows 250..260: 4 over
        ids = eng.add_docs(new)
        _, got = eng.search(new)
        np.testing.assert_array_equal(got[:, 0], ids)
        assert state.data["coded_upto"] == 256
        assert eng.backend._tail_load(state, eng.store.stats()) == 4
