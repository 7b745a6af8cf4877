"""Retrieval engine: bucketing correctness, mutable-corpus visibility, and
parity with direct progressive_search on a static corpus."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import progressive_search
from repro.engine import BucketPolicy, DocStore, RetrievalEngine

RNG = np.random.default_rng(7)
D = 32


def make_engine(n_docs=120, **kw):
    kw.setdefault("d_start", 8)
    kw.setdefault("k0", 16)
    kw.setdefault("buckets", (1, 2, 4, 8))
    kw.setdefault("capacity", 16)
    kw.setdefault("block_n", 64)
    db = RNG.normal(size=(n_docs, D)).astype(np.float32)
    eng = RetrievalEngine(D, **kw)
    eng.add_docs(db)
    return eng, db


class TestBucketPolicy:
    def test_bucket_for_rounds_up(self):
        p = BucketPolicy((1, 2, 4, 8))
        assert [p.bucket_for(n) for n in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
        assert p.bucket_for(100) == 8          # oversized -> top bucket

    def test_plan_covers_exactly(self):
        p = BucketPolicy((2, 4, 8))
        for n in range(1, 40):
            plan = p.plan(n)
            assert sum(plan) >= n
            # all but the last batch are full top-size buckets
            assert all(b == 8 for b in plan[:-1])
            assert sum(plan) - n < 8           # bounded padding

    def test_invalid_ladders_rejected(self):
        with pytest.raises(ValueError):
            BucketPolicy(())
        with pytest.raises(ValueError):
            BucketPolicy((4, 2))
        with pytest.raises(ValueError):
            BucketPolicy((0, 2))


class TestDocStore:
    def test_ids_stable_and_growth_doubles(self):
        store = DocStore(D, (8, 16, 32), capacity=4)
        a = store.add(RNG.normal(size=(3, D)).astype(np.float32))
        b = store.add(RNG.normal(size=(10, D)).astype(np.float32))
        assert a.tolist() == [0, 1, 2]
        assert b.tolist() == list(range(3, 13))
        assert store.capacity == 16 and store.n_grows >= 1
        assert store.size == 13 and store.n_active == 13

    def test_delete_is_tombstone(self):
        store = DocStore(D, (8,), capacity=8)
        ids = store.add(RNG.normal(size=(5, D)).astype(np.float32))
        assert store.delete(ids[:2]) == 2
        assert store.delete(ids[:2]) == 0      # already dead
        assert store.n_active == 3
        assert not store.is_live(int(ids[0])) and store.is_live(int(ids[4]))
        assert store.delete([4, 4, 4]) == 1    # duplicate ids count once
        assert store.n_active == 2
        with pytest.raises(IndexError):
            store.delete([99])

    def test_prefix_norms_match_batch_build(self):
        from repro.core import build_index
        dims = (8, 16, 32)
        store = DocStore(D, dims, capacity=2)
        rows = RNG.normal(size=(9, D)).astype(np.float32)
        for r in rows:                          # one-at-a-time appends
            store.add(r)
        ref = build_index(jnp.asarray(rows), dims)
        np.testing.assert_allclose(
            np.asarray(store.sq_prefix[:9]), np.asarray(ref["sq_prefix"]),
            rtol=1e-5, atol=1e-5)


class TestEngineParity:
    def test_search_matches_direct_progressive(self):
        eng, db = make_engine()
        q = db[:11] + 0.01 * RNG.normal(size=(11, D)).astype(np.float32)
        es, ei = eng.search(q)
        rs, ri = progressive_search(jnp.asarray(q), jnp.asarray(db), eng.sched)
        np.testing.assert_array_equal(ei, np.asarray(ri))
        np.testing.assert_allclose(es, np.asarray(rs), rtol=1e-5, atol=1e-5)

    def test_results_independent_of_bucket_ladder(self):
        db = RNG.normal(size=(80, D)).astype(np.float32)
        q = db[:9] + 0.01 * RNG.normal(size=(9, D)).astype(np.float32)
        outs = []
        for buckets in [(1,), (4,), (1, 2, 4, 8), (16,)]:
            eng = RetrievalEngine(D, d_start=8, k0=16, buckets=buckets,
                                  capacity=80, block_n=64)
            eng.add_docs(db)
            outs.append(eng.search(q)[1])
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    def test_empty_batch_returns_empty(self):
        eng, _ = make_engine(n_docs=20)
        s, i = eng.search(np.zeros((0, D), np.float32))
        assert s.shape == (0, eng.out_k)
        assert i.shape == (0, eng.out_k)

    def test_single_stage_schedule_honors_final_k(self):
        # d_emb <= d_start collapses the schedule to one stage that keeps k0
        # candidates; the engine must still return final_k-wide results, with
        # the same width for empty and non-empty batches.
        eng = RetrievalEngine(8, d_start=32, k0=8, final_k=1,
                              capacity=16, buckets=(2,), block_n=16)
        db = RNG.normal(size=(10, 8)).astype(np.float32)
        eng.add_docs(db)
        s, i = eng.search(db[:2])
        assert s.shape == (2, 1) and i.shape == (2, 1)
        np.testing.assert_array_equal(i[:, 0], [0, 1])
        s0, i0 = eng.search(np.zeros((0, 8), np.float32))
        assert s0.shape == (0, 1) and i0.shape == (0, 1)

    def test_search_rejects_wrong_query_dim(self):
        eng, _ = make_engine(n_docs=20)
        with pytest.raises(ValueError):
            eng.search(np.zeros((2, D + 1), np.float32))

    def test_request_path_matches_batch_search(self):
        eng, db = make_engine()
        q = db[5:12] + 0.02 * RNG.normal(size=(7, D)).astype(np.float32)
        _, direct = eng.search(q)
        rids = [eng.submit(v) for v in q]
        assert eng.n_pending == 7
        done = eng.run_until_idle()
        assert done == 7 and eng.n_pending == 0
        got = np.stack([eng.poll(r).doc_ids for r in rids])
        np.testing.assert_array_equal(got, direct)
        from repro.engine import ResultEvicted
        with pytest.raises(ResultEvicted):     # results pop once; a second
            eng.poll(rids[0])                  # poll is "gone", not "wait"

    def test_each_bucket_shape_compiles_once(self):
        eng, db = make_engine()
        for _ in range(3):
            for n in (1, 3, 7):
                eng.search(db[:n])
        # 3 distinct buckets (1, 4, 8) at one capacity -> 3 compile events
        assert eng.stats.n_compiles == 0        # search() path counts...
        assert len(eng._seen_shapes) == 3


class TestMutableCorpus:
    def test_deleted_doc_never_returned(self):
        eng, db = make_engine()
        # query IS doc 17's embedding: without deletion it must win
        q = db[17:18]
        _, before = eng.search(q)
        assert before[0, 0] == 17
        eng.delete_docs([17])
        _, after = eng.search(q)
        assert 17 not in after
        # request path agrees
        rid = eng.submit(q[0])
        eng.run_until_idle()
        assert 17 not in eng.poll(rid).doc_ids

    def test_added_doc_becomes_visible(self):
        eng, db = make_engine(n_docs=60)
        new = RNG.normal(size=(1, D)).astype(np.float32) * 5.0
        [nid] = eng.add_docs(new)
        _, idx = eng.search(new)
        assert idx[0, 0] == nid

    def test_add_beyond_capacity_keeps_results_correct(self):
        eng = RetrievalEngine(D, d_start=8, k0=8, capacity=4,
                              buckets=(4,), block_n=32)
        db = RNG.normal(size=(50, D)).astype(np.float32)
        for i in range(0, 50, 10):              # five appends, several grows
            eng.add_docs(db[i:i + 10])
        assert eng.store.capacity >= 50 and eng.store.n_grows >= 3
        _, idx = eng.search(db[:4])
        np.testing.assert_array_equal(idx[:, 0], np.arange(4))

    def test_fully_deleted_corpus_returns_sentinel(self):
        eng, db = make_engine(n_docs=10)
        eng.delete_docs(np.arange(10))
        assert eng.n_docs == 0
        scores, idx = eng.search(db[:2])
        assert (idx == -1).all()
        assert np.isinf(scores).all()

    def test_empty_tail_capacity_never_leaks(self):
        # capacity > size: unpopulated (zero) rows must not be returned,
        # even for a zero query whose nearest vector is the zero row.
        eng = RetrievalEngine(D, d_start=8, k0=8, capacity=64,
                              buckets=(1,), block_n=64)
        db = (RNG.normal(size=(5, D)).astype(np.float32)
              + 10.0)                            # far from the origin
        eng.add_docs(db)
        _, idx = eng.search(np.zeros((1, D), np.float32))
        assert 0 <= idx[0, 0] < 5


class TestPipelineCorpusSync:
    """RAGPipeline must keep engine ids and doc_tokens rows aligned."""

    def _pipe(self):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import LMConfig
        from repro.models import lm as LM
        from repro.rag import RAGPipeline
        from repro.rag.pipeline import mean_pool_embedder
        cfg = LMConfig(name="t", n_layers=1, d_model=32, n_heads=2,
                       n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
                       param_dtype="float32", compute_dtype="float32",
                       remat=False)
        params = LM.init_lm(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(
            np.random.default_rng(0).integers(1, 128, (6, 5)), jnp.int32)
        db = mean_pool_embedder(params, cfg)(toks)
        return RAGPipeline(params, cfg, db, toks, d_start=4, k0=4), db, toks

    def test_add_docs_validates_before_mutating(self):
        pipe, db, toks = self._pipe()
        with pytest.raises(ValueError):        # count mismatch
            pipe.add_docs(np.asarray(db[:2]), np.asarray(toks[:1]))
        with pytest.raises(ValueError):        # width mismatch
            pipe.add_docs(np.asarray(db[:1]),
                          np.zeros((1, 9), np.int32))
        # failed validation must not have touched the engine
        assert pipe.engine.store.size == 6

    def test_sentinel_prepends_padding_not_doc0(self):
        import jax.numpy as jnp
        pipe, db, toks = self._pipe()
        prompts = pipe.assemble_prompts(
            jnp.asarray(toks[:1]), np.asarray([[-1]], np.int32))
        doc_len = toks.shape[1]
        assert (np.asarray(prompts)[0, :doc_len] == 0).all()

    def test_zero_doc_corpus_serves(self):
        import jax.numpy as jnp
        pipe, db, toks = self._pipe()
        pipe.delete_docs(list(range(6)))
        out = pipe.serve(jnp.asarray(toks[:1]), max_new_tokens=2)
        assert out["retrieved"][0, 0] == -1
        assert out["generated"].shape == (1, 2)

    def test_driver_path_matches_sync_path(self):
        import jax.numpy as jnp
        pipe, db, toks = self._pipe()
        q = jnp.asarray(toks[:3])
        _, sync_ids = pipe.retrieve(q)
        pipe.start_driver(max_wait_ms=0.5)
        try:
            _, driver_ids = pipe.retrieve(q)
            np.testing.assert_array_equal(driver_ids, sync_ids)
        finally:
            pipe.stop_driver()
        # driver gone: back to the synchronous path
        _, after = pipe.retrieve(q)
        np.testing.assert_array_equal(after, sync_ids)

    def test_driver_results_refreshed_when_compaction_races_delivery(self):
        """A compaction landing between a driver dispatch and the pipeline's
        gather must not leak pre-remap doc ids: retrieve() detects the stale
        store_generation and re-searches under engine.lock."""
        import jax.numpy as jnp
        pipe, db, toks = self._pipe()
        eng = pipe.engine
        pipe.start_driver(max_wait_ms=0.5)
        try:
            # interpose on the driver's dispatch: right after the batch runs
            # (results already stamped with the pre-compaction generation),
            # delete half the corpus and force the compaction+rebuild —
            # deterministic stand-in for a racing mutator thread
            orig, fired = eng.execute_batch, []

            def tampered(reqs):
                out = orig(reqs)
                if not fired:
                    fired.append(True)
                    eng.delete_docs([3, 4, 5])   # dead_frac 0.5 >= 0.3
                    eng.maybe_rebuild(force=True)
                return out

            eng.execute_batch = tampered
            try:
                _, ids = pipe.retrieve(jnp.asarray(toks[:3]))
            finally:
                eng.execute_batch = orig
            assert eng.stats.n_compactions == 1
            # ids must be post-remap: valid rows of the shrunken token table
            assert (ids < pipe.doc_tokens.shape[0]).all()
            _, expected = pipe.retrieve(jnp.asarray(toks[:3]))
            np.testing.assert_array_equal(ids, expected)
        finally:
            pipe.stop_driver()

    def test_conflicting_engine_args_rejected(self):
        import jax
        import jax.numpy as jnp
        from repro.engine import RetrievalEngine
        from repro.rag import RAGPipeline
        pipe, db, toks = self._pipe()
        params, cfg = pipe.lm_params, pipe.cfg
        eng = RetrievalEngine(db.shape[1], d_start=4, k0=4, capacity=8)
        with pytest.raises(ValueError):
            RAGPipeline(params, cfg, db, toks, engine=eng, buckets=(64,))


class TestStatsAndProfile:
    def test_request_stats_fields(self):
        eng, db = make_engine()
        eng.search(db[:1])                     # warm the bucket-1 shape
        rid = eng.submit(db[0])
        eng.step()
        res = eng.poll(rid)
        st = res.stats
        assert not st.compiled
        assert st.latency_ms >= st.queue_ms >= 0
        assert st.compute_ms > 0
        assert st.bucket >= st.batch_fill == 1
        s = eng.stats.summary()
        assert s["n_completed"] == 1 and s["n_batches"] == 1
        assert np.isfinite(s["latency_ms_p50"])

    def test_compiled_batches_excluded_from_percentiles(self):
        eng, db = make_engine()
        rid = eng.submit(db[0])                # cold shape: compile event
        eng.step()
        assert eng.poll(rid).stats.compiled
        s = eng.stats.summary()
        assert s["n_compiles"] == 1 and s["n_completed"] == 1
        assert not np.isfinite(s["latency_ms_p50"])  # no steady samples yet

    def test_submit_rejects_matrix_query(self):
        eng, db = make_engine()
        with pytest.raises(ValueError):        # (4, 8) flattens to D=32 but
            eng.submit(db[0].reshape(4, 8))    # is not a query vector
        eng.submit(db[0:1])                    # (1, D) is accepted

    def test_padding_accounted(self):
        eng, db = make_engine()
        for v in db[:3]:
            eng.submit(v)
        eng.run_until_idle()
        # 3 requests -> one bucket-4 batch with 1 padded slot
        assert eng.stats.n_padded_slots == 1
