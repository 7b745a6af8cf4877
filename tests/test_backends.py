"""Index-backend subsystem: protocol registry, recall vs the flat baseline,
add/delete-then-rebuild correctness, tail injection, compaction remaps, and
the background-build lifecycle."""

import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import truncated_search, overlap_at_k
from repro.core.ivf import balanced_assign
from repro.engine import DocStore, RetrievalEngine
from repro.index_backends import (
    FlatProgressiveBackend,
    IndexBackend,
    StoreStats,
    backend_names,
    make_backend,
)

RNG = np.random.default_rng(11)
D = 32
REGISTERED = ("flat", "ivf", "quantized")
# "ivf_kernel" is the ivf backend with the fused Pallas stage-0 scan forced
# (interpret mode on CPU), "ivf_pq" composes it with PQ member slabs, and
# "quantized_pq" is the quantized backend's ADC codec — every variant must
# pass the identical engine contract
BACKENDS = REGISTERED + ("ivf_kernel", "ivf_pq", "quantized_pq")


def opts_for(backend, **extra):
    base = {
        "flat": {},
        # small corpora: force real clustering instead of the flat fallback
        "ivf": dict(n_lists=12, n_probe=6, min_index_rows=32,
                    min_rebuild_rows=16),
        "ivf_kernel": dict(n_lists=12, n_probe=6, min_index_rows=32,
                           min_rebuild_rows=16, use_kernel=True,
                           kernel_block_m=16),
        "ivf_pq": dict(n_lists=12, n_probe=6, min_index_rows=32,
                       min_rebuild_rows=16, use_kernel=True,
                       kernel_block_m=16, stage0_dtype="pq"),
        "quantized": dict(min_rebuild_rows=16),
        "quantized_pq": dict(min_rebuild_rows=16, codec="pq"),
    }[backend]
    return {**base, **extra} or None


def engine_backend(backend):
    if backend.startswith("ivf"):
        return "ivf"
    if backend.startswith("quantized"):
        return "quantized"
    return backend


def make_engine(backend, n_docs=200, seed=7, **kw):
    opts = kw.pop("backend_opts", opts_for(backend))
    kw.setdefault("d_start", 8)
    kw.setdefault("k0", 16)
    kw.setdefault("buckets", (4,))
    kw.setdefault("capacity", 64)
    kw.setdefault("block_n", 64)
    eng = RetrievalEngine(D, backend=engine_backend(backend),
                          backend_opts=opts, **kw)
    db = np.random.default_rng(seed).normal(size=(n_docs, D)).astype(np.float32)
    eng.add_docs(db)
    return eng, db


class TestRegistry:
    def test_names(self):
        assert set(REGISTERED) <= set(backend_names())

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown index backend"):
            RetrievalEngine(D, backend="hnsw")

    def test_instance_passthrough_and_opts_conflict(self):
        from repro.core import make_schedule
        sched = make_schedule(8, D, 16)
        be = FlatProgressiveBackend(sched)
        assert make_backend(be, sched=sched) is be
        with pytest.raises(ValueError):
            make_backend(be, sched=sched, n_probe=4)

    def test_bad_rebuild_mode_rejected(self):
        with pytest.raises(ValueError, match="rebuild_mode"):
            RetrievalEngine(D, rebuild_mode="eager")

    def test_quantized_rejects_cosine(self):
        with pytest.raises(ValueError, match="l2"):
            RetrievalEngine(D, backend="quantized", metric="cosine")


@pytest.mark.parametrize("backend", BACKENDS)
class TestBackendEngineSuite:
    """Every backend must pass the same search/add/delete/rebuild contract."""

    def test_exact_query_self_retrieval(self, backend):
        eng, db = make_engine(backend)
        _, idx = eng.search(db[:8])
        np.testing.assert_array_equal(idx[:, 0], np.arange(8))

    def test_deleted_doc_never_returned(self, backend):
        eng, db = make_engine(backend)
        _, before = eng.search(db[17:18])
        assert before[0, 0] == 17
        eng.delete_docs([17])
        _, after = eng.search(db[17:18])
        assert 17 not in after
        rid = eng.submit(db[17])
        eng.run_until_idle()
        assert 17 not in eng.poll(rid).doc_ids

    def test_added_doc_visible_without_rebuild(self, backend):
        # tail injection: a doc appended after the index build must be
        # retrievable before any rebuild happens
        eng, db = make_engine(backend)
        eng.search(db[:1])                      # force the initial build
        n_rebuilds = eng.stats.n_rebuilds
        new = RNG.normal(size=(1, D)).astype(np.float32) * 5.0
        [nid] = eng.add_docs(new)
        _, idx = eng.search(new)
        assert idx[0, 0] == nid
        assert eng.stats.n_rebuilds == n_rebuilds

    def test_delete_survives_rebuild(self, backend):
        eng, db = make_engine(backend)
        eng.delete_docs([5])
        _, idx = eng.search(db[5:6])
        assert 5 not in idx
        assert eng.maybe_rebuild(force=True)
        _, idx = eng.search(db[5:6])
        assert 5 not in idx
        assert eng.index_state.built_active == len(db) - 1

    def test_churn_triggers_natural_rebuild(self, backend):
        eng, db = make_engine(backend)
        eng.search(db[:1])
        n_rebuilds = eng.stats.n_rebuilds
        # exceed min_rebuild_rows (flat never rebuilds by design)
        extra = RNG.normal(size=(80, D)).astype(np.float32)
        ids = eng.add_docs(extra)
        _, idx = eng.search(extra[:4])
        np.testing.assert_array_equal(idx[:, 0], ids[:4])
        if backend == "flat":
            assert eng.stats.n_rebuilds == n_rebuilds
        else:
            assert eng.stats.n_rebuilds > n_rebuilds

    def test_fully_deleted_corpus_returns_sentinel(self, backend):
        eng, db = make_engine(backend, n_docs=40)
        eng.delete_docs(np.arange(40))
        scores, idx = eng.search(db[:2])
        assert (idx == -1).all()
        assert np.isinf(scores).all()

    def test_tail_overflow_forces_rebuild_even_when_off(self, backend):
        if backend == "flat":
            pytest.skip("flat covers every row; no tail window")
        # append_spare=0 / encode_appends=False turn incremental absorption
        # off (where supported), so appends land in the tail window and the
        # hard bound must fire
        opts = opts_for(backend, min_rebuild_rows=4, rebuild_frac=0.01)
        if "ivf" in backend:
            opts["append_spare"] = 0
        if backend.startswith("quantized"):
            opts["encode_appends"] = False
        eng, db = make_engine(backend, backend_opts=opts,
                              rebuild_mode="off")
        eng.search(db[:1])
        n_rebuilds = eng.stats.n_rebuilds
        extra = RNG.normal(size=(12, D)).astype(np.float32)  # > tail_cap=4
        ids = eng.add_docs(extra)
        _, idx = eng.search(extra)
        np.testing.assert_array_equal(idx[:, 0], ids)
        assert eng.stats.n_rebuilds > n_rebuilds


@pytest.mark.parametrize(
    "backend", ("ivf", "ivf_kernel", "ivf_pq", "quantized", "quantized_pq"))
class TestRecall:
    def test_recall_vs_flat_on_clustered_corpus(self, backend):
        from repro.rag import make_clustered_corpus
        c = make_clustered_corpus(n_docs=1536, dim=64, n_queries=32,
                                  n_clusters=24, seed=5)
        _, exact = truncated_search(
            jnp.asarray(c.queries), jnp.asarray(c.db), dim=64, k=10,
            block_n=1536)

        def run(be, opts):
            eng = RetrievalEngine(
                64, d_start=16, k0=64, final_k=10, buckets=(32,),
                capacity=1536, block_n=1536, backend=be, backend_opts=opts)
            eng.add_docs(c.db)
            _, ids = eng.search(c.queries)
            return float(overlap_at_k(jnp.asarray(ids), exact, 10))

        flat = run("flat", None)
        opts = None
        if "ivf" in backend:
            opts = dict(n_lists=24, n_probe=8, min_index_rows=32)
            if backend in ("ivf_kernel", "ivf_pq"):
                opts["use_kernel"] = True
            if backend == "ivf_pq":
                opts["stage0_dtype"] = "pq"
        elif backend == "quantized_pq":
            opts = dict(codec="pq")
        approx = run(engine_backend(backend), opts)
        assert flat >= 0.9                       # schedule is wide enough
        # approximate backends stay within 10 points of the exact baseline
        assert approx >= flat - 0.10


class TestCompaction:
    def test_store_compact_unit(self):
        dims = (8, 16, 32)
        store = DocStore(D, dims, capacity=4)
        rows = RNG.normal(size=(10, D)).astype(np.float32)
        store.add(rows)
        store.delete([0, 3, 4, 9])
        id_map = store.compact()
        assert store.size == store.n_active == 6
        assert store.capacity == 8               # pow2 shrink from 16
        assert store.n_compactions == 1
        live_old = [1, 2, 5, 6, 7, 8]
        np.testing.assert_array_equal(id_map[live_old], np.arange(6))
        assert (id_map[[0, 3, 4, 9]] == -1).all()
        np.testing.assert_allclose(
            np.asarray(store.db[:6]), rows[live_old], rtol=1e-6)
        # prefix norms must match a fresh build over the surviving rows
        from repro.core import build_index
        ref = build_index(jnp.asarray(rows[live_old]), dims)
        np.testing.assert_allclose(
            np.asarray(store.sq_prefix[:6]), np.asarray(ref["sq_prefix"]),
            rtol=1e-5, atol=1e-5)
        # lifetime counters keep their pre-compaction history
        assert store.total_added == 10 and store.total_deleted == 4

    def test_engine_compacts_and_remaps(self):
        eng, db = make_engine("flat", n_docs=100, compact_dead_frac=0.4)
        # an unpolled result that must be remapped across the compaction
        rid = eng.submit(db[60])
        eng.run_until_idle()
        eng.delete_docs(np.arange(50))           # 50% dead
        maps = []
        eng.on_remap.append(maps.append)
        _, idx = eng.search(db[60:61])
        assert eng.stats.n_compactions == 1 and len(maps) == 1
        assert eng.store.size == 50
        assert idx[0, 0] == 10                   # doc 60 slid down by 50
        res = eng.poll(rid)
        assert res.doc_ids[0] == 10              # unpolled result followed

    def test_no_compaction_below_threshold(self):
        eng, db = make_engine("flat", n_docs=100, compact_dead_frac=0.4)
        eng.delete_docs(np.arange(10))
        eng.search(db[50:51])
        assert eng.stats.n_compactions == 0

    def test_compaction_survives_raising_remap_callback(self):
        # a failing on_remap callback must not leave a pre-compaction index
        # state serving remapped buffers (silently wrong documents): the
        # engine rebuilds first, then the callback's error reaches the caller
        eng, db = make_engine("ivf", n_docs=120, compact_dead_frac=0.3)
        eng.search(db[:1])

        def boom(id_map):
            raise RuntimeError("callback failed")

        eng.on_remap.append(boom)
        eng.delete_docs(np.arange(0, 120, 2))
        with pytest.raises(RuntimeError, match="callback failed"):
            eng.search(db[1:2])
        eng.on_remap.remove(boom)
        assert eng.stats.n_compactions == 1
        _, idx = eng.search(db[1:7:2])           # odd (surviving) docs
        np.testing.assert_array_equal(idx[:, 0], [0, 1, 2])

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_post_compaction_search_correct(self, backend):
        eng, db = make_engine(backend, n_docs=120, compact_dead_frac=0.3)
        eng.search(db[:1])
        eng.delete_docs(np.arange(0, 120, 2))    # half the corpus
        _, idx = eng.search(db[1:7:2])           # odd (surviving) docs
        assert eng.stats.n_compactions == 1
        # old ids 1,3,5 -> compacted ids 0,1,2
        np.testing.assert_array_equal(idx[:, 0], [0, 1, 2])

    @staticmethod
    def _make_pipe(doc_tokens):
        import jax
        from repro.configs.base import LMConfig
        from repro.models import lm as LM
        from repro.rag import RAGPipeline
        from repro.rag.pipeline import mean_pool_embedder
        cfg = LMConfig(name="t", n_layers=1, d_model=32, n_heads=2,
                       n_kv_heads=2, d_head=16, d_ff=64, vocab=128,
                       param_dtype="float32", compute_dtype="float32",
                       remat=False)
        params = LM.init_lm(jax.random.PRNGKey(0), cfg)
        db = mean_pool_embedder(params, cfg)(jnp.asarray(doc_tokens))
        return RAGPipeline(params, cfg, db, doc_tokens, d_start=4, k0=4), db

    def test_pipeline_tokens_follow_compaction(self):
        toks = jnp.asarray(
            np.random.default_rng(0).integers(1, 128, (12, 5)), jnp.int32)
        pipe, _ = self._make_pipe(toks)
        pipe.delete_docs(list(range(8)))         # > default compact frac
        target = np.asarray(toks[10:11])
        _, idx = pipe.retrieve(jnp.asarray(target))
        assert pipe.engine.stats.n_compactions == 1
        # retrieved id indexes the REMAPPED token table, same text comes back
        np.testing.assert_array_equal(
            pipe.doc_tokens[idx[0, 0]], target[0])

    def test_compaction_never_writes_through_caller_tokens(self):
        # the constructor aliases a writable caller array; the remap must
        # copy-on-write instead of shuffling the caller's rows in place
        toks = np.random.default_rng(0).integers(
            1, 128, (12, 5)).astype(np.int32)
        before = toks.copy()
        pipe, _ = self._make_pipe(toks)
        pipe.delete_docs(list(range(8)))
        pipe.retrieve(jnp.asarray(toks[10:11]))
        assert pipe.engine.stats.n_compactions == 1
        np.testing.assert_array_equal(toks, before)

    def test_pipeline_rejects_backend_conflicting_with_engine(self):
        toks = jnp.asarray(
            np.random.default_rng(0).integers(1, 128, (6, 5)), jnp.int32)
        pipe, db = self._make_pipe(toks)
        from repro.rag import RAGPipeline
        eng = RetrievalEngine(db.shape[1], d_start=4, k0=4, capacity=8)
        with pytest.raises(ValueError, match="backend"):
            RAGPipeline(pipe.lm_params, pipe.cfg, db, toks, engine=eng,
                        backend="ivf")


class TestBackgroundRebuild:
    def _wait_rebuild(self, eng, n_before, timeout=30.0):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            eng.maybe_rebuild()                  # adopt when ready
            if eng.stats.n_rebuilds > n_before:
                return True
            time.sleep(0.02)
        return False

    def test_background_build_adopts_state(self):
        # soft threshold <= rows added (16) <= tail window (32): the
        # rebuild is wanted but not correctness-forced -> background path
        opts = opts_for("ivf", min_rebuild_rows=8, rebuild_frac=0.05)
        eng, db = make_engine("ivf", backend_opts=opts,
                              rebuild_mode="background")
        eng.search(db[:1])
        n_before = eng.stats.n_rebuilds
        built_size_before = eng.index_state.built_size
        extra = RNG.normal(size=(16, D)).astype(np.float32)
        ids = eng.add_docs(extra)
        _, idx = eng.search(extra[:4])           # serves old state + tail
        np.testing.assert_array_equal(idx[:, 0], ids[:4])
        assert self._wait_rebuild(eng, n_before)
        assert eng.index_state.built_size > built_size_before
        _, idx = eng.search(extra[:4])           # new state agrees
        np.testing.assert_array_equal(idx[:, 0], ids[:4])


    def test_stale_background_build_never_reverts_newer_state(self):
        opts = opts_for("ivf", min_rebuild_rows=8, rebuild_frac=0.05)
        eng, db = make_engine("ivf", backend_opts=opts,
                              rebuild_mode="background")
        eng.search(db[:1])
        eng.add_docs(RNG.normal(size=(16, D)).astype(np.float32))
        eng.search(db[:1])                       # launches background build
        ids = eng.add_docs(RNG.normal(size=(4, D)).astype(np.float32))
        eng.maybe_rebuild(force=True)            # newer sync state lands
        forced = eng.index_state
        t0 = time.perf_counter()
        while not eng._bg.idle and time.perf_counter() - t0 < 30:
            eng.maybe_rebuild()                  # offers the stale build
            time.sleep(0.02)
        assert eng._bg.idle
        # the finished background build predates the forced one: rejected
        assert eng.index_state.generation >= forced.generation
        assert eng.index_state.built_size >= forced.built_size
        _, idx = eng.search(db[:2])              # still serving correctly
        np.testing.assert_array_equal(idx[:, 0], [0, 1])
        assert eng.store.is_live(int(ids[0]))


class TestIncrementalAbsorb:
    """Incremental IVF maintenance: appended rows join their nearest
    centroid's spare list slots between rebuilds; only rows whose list is
    full ride the tail window, and the rebuild bounds count only those."""

    def _build(self, n_docs=96, **opts):
        from repro.core import make_schedule
        sched = make_schedule(8, D, 16)
        base = dict(n_lists=8, n_probe=8, min_index_rows=16,
                    balance_factor=1.0, append_spare=4, tail_window=16,
                    min_rebuild_rows=4, rebuild_frac=10.0)  # churn disabled
        base.update(opts)
        be = make_backend("ivf", sched=sched, **base)
        store = DocStore(D, (8, 16, 32), capacity=128)
        store.add(RNG.normal(size=(n_docs, D)).astype(np.float32))
        state = be.build(store.db, store.valid,
                         sq_prefix=store.sq_prefix, stats=store.stats())
        return be, store, state

    def _absorb(self, be, store, state):
        be.absorb_appends(state, store.db, store.valid,
                          sq_prefix=store.sq_prefix, stats=store.stats())

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_appends_absorbed_into_lists(self, use_kernel):
        be, store, state = self._build(use_kernel=use_kernel,
                                       kernel_block_m=16)
        new = RNG.normal(size=(4, D)).astype(np.float32) * 3
        ids = store.add(new)
        self._absorb(be, store, state)
        assert state.data["absorb_upto"] == store.size
        assert len(state.data["tail_pending"]) == 0
        # reachable through the LISTS: the tail window is empty
        assert (be._tail_ids(state, store.size) == -1).all()
        _, idx = be.search(jnp.asarray(new), state, store.db, store.valid,
                           sq_prefix=store.sq_prefix, n_total=store.size,
                           k=1)
        np.testing.assert_array_equal(np.asarray(idx)[:, 0], ids)
        assert not be.must_rebuild(state, store.stats())
        assert not be.needs_rebuild(state, store.stats())

    def test_full_lists_overflow_to_tail_then_force_rebuild(self):
        be, store, state = self._build()
        # total list capacity is 8 lists x 16 slots = 128; 96 built rows
        # leave at most 32 free slots, so 60 appends must overflow
        store.add(RNG.normal(size=(60, D)).astype(np.float32))
        self._absorb(be, store, state)
        assert state.data["absorb_upto"] == store.size
        pend = state.data["tail_pending"]
        assert len(pend) >= 60 - 32
        # the overflow exceeds the tail window: the hard bound fires — an
        # engine would rebuild before the next dispatch
        assert be.must_rebuild(state, store.stats())

    def test_absorb_disabled_with_zero_spare(self):
        be, store, state = self._build(append_spare=0)
        store.add(RNG.normal(size=(4, D)).astype(np.float32))
        self._absorb(be, store, state)
        assert state.data["absorb_upto"] == 96      # untouched
        # appended rows still reachable — via the tail window
        tail = be._tail_ids(state, store.size)
        np.testing.assert_array_equal(tail[:4], np.arange(96, 100))

    def test_tombstoned_pending_rows_pruned(self):
        be, store, state = self._build()
        store.add(RNG.normal(size=(60, D)).astype(np.float32))
        self._absorb(be, store, state)
        pend = state.data["tail_pending"]
        assert len(pend) > 0
        store.delete(pend.tolist())
        self._absorb(be, store, state)              # no new rows; prunes
        # deleted pending rows no longer hold tail-window capacity
        assert len(state.data["tail_pending"]) == 0

    @pytest.mark.parametrize("backend", ("ivf", "ivf_kernel"))
    def test_engine_absorbs_appends_without_rebuild(self, backend):
        eng, db = make_engine(backend)
        eng.search(db[:1])                          # initial build
        n_rb = eng.stats.n_rebuilds
        new = RNG.normal(size=(8, D)).astype(np.float32) * 4
        ids = eng.add_docs(new)
        _, idx = eng.search(new)
        np.testing.assert_array_equal(idx[:, 0], ids)
        st = eng.index_state
        assert st.data["absorb_upto"] == eng.store.size
        assert len(st.data["tail_pending"]) == 0
        assert eng.stats.n_rebuilds == n_rb
        # a deleted absorbed row is unreturnable immediately
        eng.delete_docs([int(ids[0])])
        _, idx = eng.search(new[:1])
        assert int(ids[0]) not in idx


class TestStaleness:
    def test_needs_rebuild_thresholds(self):
        from repro.core import make_schedule
        sched = make_schedule(8, D, 16)
        be = make_backend("ivf", sched=sched, n_lists=4,
                          rebuild_frac=0.5, min_rebuild_rows=10,
                          min_index_rows=4)
        store = DocStore(D, (8, 16, 32), capacity=64)
        store.add(RNG.normal(size=(40, D)).astype(np.float32))
        state = be.build(store.db, store.valid, sq_prefix=store.sq_prefix,
                         stats=store.stats())
        assert not be.needs_rebuild(state, store.stats())
        store.delete(np.arange(5))               # churn 5 < 20
        assert not be.needs_rebuild(state, store.stats())
        store.add(RNG.normal(size=(15, D)).astype(np.float32))
        assert be.needs_rebuild(state, store.stats())  # churn 20 >= 20

    def test_stats_properties(self):
        st = StoreStats(size=10, n_active=6, capacity=16, generation=3,
                        total_added=10, total_deleted=4)
        assert st.n_dead == 4
        assert st.dead_frac == pytest.approx(0.4)
        assert StoreStats(0, 0, 1, 0, 0, 0).dead_frac == 0.0


class TestIndexCheckpoint:
    """Persist/restore built index state through `repro.checkpoint`:
    serving restarts skip the k-means / codebook builds."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_round_trip_identical_results(self, backend, tmp_path):
        eng, db = make_engine(backend)
        s1, i1 = eng.search(db[:8])
        eng.save_index(str(tmp_path))

        eng2, _ = make_engine(backend)              # same corpus, no build
        assert eng2.load_index(str(tmp_path))
        assert eng2.stats.n_rebuilds == 0           # the point of loading
        s2, i2 = eng2.search(db[:8])
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_allclose(s1, s2, rtol=1e-5, atol=1e-5)
        # staleness restarts clean: nothing to rebuild right after load
        assert not eng2.backend.needs_rebuild(
            eng2.index_state, eng2.store.stats())

    @pytest.mark.parametrize("backend", ("ivf", "quantized_pq"))
    def test_loaded_state_serves_mutations(self, backend, tmp_path):
        eng, db = make_engine(backend)
        eng.search(db[:1])
        eng.save_index(str(tmp_path))
        eng2, _ = make_engine(backend)
        assert eng2.load_index(str(tmp_path))
        new = RNG.normal(size=(3, D)).astype(np.float32) * 5.0
        ids = eng2.add_docs(new)
        _, got = eng2.search(new)
        np.testing.assert_array_equal(got[:, 0], ids)
        eng2.delete_docs([7])
        _, after = eng2.search(db[7:8])
        assert 7 not in after

    def test_missing_checkpoint_returns_false(self, tmp_path):
        eng, _ = make_engine("flat")
        assert not eng.load_index(str(tmp_path / "nope"))

    def test_backend_kind_mismatch_raises(self, tmp_path):
        eng, db = make_engine("ivf")
        eng.search(db[:1])
        eng.save_index(str(tmp_path))
        eng2, _ = make_engine("quantized")
        with pytest.raises(ValueError, match="backend"):
            eng2.load_index(str(tmp_path))

    def test_codec_mismatch_raises(self, tmp_path):
        eng, db = make_engine("quantized_pq")
        eng.search(db[:1])
        eng.save_index(str(tmp_path))
        eng2, _ = make_engine("quantized")
        with pytest.raises(ValueError, match="codec"):
            eng2.load_index(str(tmp_path))

    def test_oversized_index_rejected(self, tmp_path):
        eng, db = make_engine("ivf")
        eng.search(db[:1])
        eng.save_index(str(tmp_path))
        eng2, _ = make_engine("ivf", n_docs=20)     # smaller corpus
        with pytest.raises(ValueError, match="re-add the corpus"):
            eng2.load_index(str(tmp_path))


class TestBalancedAssign:
    def test_respects_cap_and_preference(self):
        choices = np.array([[0, 1], [0, 1], [0, 1], [1, 0]])
        order = np.arange(4)
        assign = balanced_assign(choices, order, n_lists=2, cap=2)
        counts = np.bincount(assign, minlength=2)
        assert (counts <= 2).all() and counts.sum() == 4
        # first two (most confident) rows keep their first choice
        assert assign[0] == 0 and assign[1] == 0
        assert assign[3] == 1                    # its own first choice

    def test_overflow_rows_spill_to_free_lists(self):
        choices = np.zeros((6, 1), np.int64)     # everyone wants list 0
        assign = balanced_assign(choices, np.arange(6), n_lists=3, cap=2)
        assert (np.bincount(assign, minlength=3) == 2).all()

    def test_overflow_rows_take_nearest_list_with_room(self):
        # every row's one choice is list 0; beyond it, rows rank list 3
        # above lists 1 and 2, so the overflow fills list 3 first and only
        # then list 1 (never the lowest-indexed free list first)
        choices = np.zeros((6, 1), np.int64)
        full = np.tile([0, 3, 1, 2], (6, 1))
        seen = []

        def rank_rest(rows):
            seen.append(rows.copy())
            return full[rows]

        assign = balanced_assign(choices, np.arange(6)[::-1], n_lists=4,
                                 cap=2, rank_rest=rank_rest)
        np.testing.assert_array_equal(assign, [1, 1, 3, 3, 0, 0])
        # asked once, for the displaced rows in confidence order
        np.testing.assert_array_equal(seen[0], [3, 2, 1, 0])
        assert len(seen) == 1

    def test_impossible_cap_raises(self):
        with pytest.raises(ValueError):
            balanced_assign(np.zeros((5, 1), np.int64), np.arange(5),
                            n_lists=2, cap=2)


class TestProtocolSubclass:
    def test_custom_backend_pluggable(self):
        # the protocol is the extension point: a trivial user backend that
        # delegates to flat must slot into the engine unchanged
        from repro.core import make_schedule

        class EchoBackend(FlatProgressiveBackend):
            name = "echo-test"

        sched = make_schedule(8, D, 16)
        eng = RetrievalEngine(D, d_start=8, k0=16, capacity=32,
                              buckets=(2,), block_n=32,
                              backend=EchoBackend(sched))
        db = RNG.normal(size=(20, D)).astype(np.float32)
        eng.add_docs(db)
        _, idx = eng.search(db[:2])
        np.testing.assert_array_equal(idx[:, 0], [0, 1])
        assert isinstance(eng.backend, IndexBackend)


class TestDriverCompactionInterleave:
    """Compaction/background rebuilds racing in-flight driver requests.

    The engine compacts at safe points *between* driver dispatches; every id
    a client polls must survive the remap protocol — an ``on_remap``
    subscriber applying the engine's id maps to previously-returned ids must
    always land on a valid row (or the -1 tombstone sentinel), never out of
    range.  Regression for the driver/rebuild safe-point composition.
    """

    @pytest.mark.slow
    @pytest.mark.parametrize("rebuild_mode", ("sync", "background"))
    def test_polled_ids_survive_remap_under_driver_traffic(self, rebuild_mode):
        import threading

        from repro.engine import EngineDriver

        eng = RetrievalEngine(
            D, d_start=8, k0=16, buckets=(1, 2, 4), capacity=1024,
            block_n=64, backend="flat", rebuild_mode=rebuild_mode,
            compact_dead_frac=0.2,
        )
        rng = np.random.default_rng(5)
        base = rng.normal(size=(120, D)).astype(np.float32)
        eng.add_docs(base)
        eng.warmup()

        # on_remap subscriber: replays every engine id map over all ids the
        # clients registered so far (same protocol RAGPipeline relies on)
        polled = []                       # mutated under eng.lock only
        last_remap_gen = [0]              # store generation of last remap
        def follow_remap(id_map):
            for ids in polled:
                live = ids >= 0
                assert ids[live].max(initial=-1) < id_map.shape[0]
                ids[live] = id_map[ids[live]]
            last_remap_gen[0] = eng.store.generation
        eng.on_remap.append(follow_remap)

        errors = []

        def client(seed):
            r = np.random.default_rng(seed)
            try:
                for _ in range(12):
                    res = driver.retrieve(base[r.integers(len(base))],
                                          timeout=30.0)
                    ids = np.array(res.doc_ids, np.int64)
                    with eng.lock:        # serialize vs compaction remaps
                        if res.store_generation < last_remap_gen[0]:
                            # a compaction landed between dispatch and this
                            # registration: the ids predate a map we never
                            # saw — exactly what store_generation exists to
                            # detect.  A real client would re-retrieve.
                            continue
                        assert (ids < eng.store.size).all()
                        polled.append(ids)
            except Exception as e:
                errors.append(e)

        with EngineDriver(eng, max_wait_ms=1.0) as driver:
            threads = [threading.Thread(target=client, args=(i,), daemon=True)
                       for i in range(4)]
            for t in threads:
                t.start()
            # deletes push dead_frac past compact_dead_frac repeatedly while
            # clients are in flight; adds keep the corpus from emptying
            for round_ in range(4):
                with eng.lock:
                    # snapshot + delete atomically: a driver-thread
                    # compaction between them would remap the snapshot's ids
                    # out from under the delete (the lock is reentrant)
                    live = [i for i in range(eng.store.size)
                            if eng.store.is_live(i)]
                    eng.delete_docs(live[:len(live) // 3])
                eng.add_docs(rng.normal(size=(20, D)).astype(np.float32))
            for t in threads:
                t.join(timeout=30.0)
                assert not t.is_alive(), "client thread hung"
        assert not errors, errors[:3]
        assert eng.stats.n_compactions >= 1, "no compaction ever triggered"
        assert polled, "every result raced a compaction — nothing verified"
        # after all remaps: every recorded id is -1 or an in-range row
        for ids in polled:
            live = ids[ids >= 0]
            assert (live < eng.store.size).all()


@pytest.mark.parametrize("backend", BACKENDS)
class TestTenantIsolation:
    """A search under tenant A never returns tenant B's (or the tenantless
    pool's) docs.  The constraint is one bitmask AND in the dispatch path —
    backend-independent by construction — so every variant must pass the
    identical contract, including across deletes and compaction remaps."""

    def test_search_scoped_to_own_tenant(self, backend):
        eng, db = make_engine(backend)            # 200 tenantless docs
        rng = np.random.default_rng(3)
        a = rng.normal(size=(40, D)).astype(np.float32)
        b = rng.normal(size=(40, D)).astype(np.float32)
        ids_a = set(eng.add_docs(a, tenant="A").tolist())
        ids_b = set(eng.add_docs(b, tenant="B").tolist())
        # querying with B's own vectors under tenant A is the adversarial
        # case: the nearest rows by geometry all belong to B
        _, idx = eng.search(b[:8], tenant="A")
        hit = set(int(i) for i in idx.ravel() if i >= 0)
        assert hit and hit <= ids_a
        assert not hit & ids_b
        # exact self-retrieval still works inside the namespace
        _, idx = eng.search(a[:8], tenant="A")
        np.testing.assert_array_equal(idx[:, 0], sorted(ids_a)[:8])

    def test_unknown_tenant_matches_nothing(self, backend):
        eng, db = make_engine(backend)
        scores, idx = eng.search(db[:4], tenant="never-added")
        assert (idx == -1).all()
        assert np.isinf(scores).all()

    def test_metadata_filter_composes_with_tenant(self, backend):
        eng, _ = make_engine(backend, n_docs=32)
        rng = np.random.default_rng(4)
        vecs = rng.normal(size=(30, D)).astype(np.float32)
        meta = [{"shard": j % 3, "lang": "en" if j % 2 else "de"}
                for j in range(30)]
        eng.add_docs(vecs, tenant="A", metadata=meta)
        eng.add_docs(vecs, tenant="B", metadata=meta)
        _, idx = eng.search(vecs[:6], tenant="A",
                            filter={"shard": {"$eq": 1}, "lang": "en"})
        hit = [int(i) for i in idx.ravel() if i >= 0]
        assert hit
        for i in hit:
            assert eng.store.tenant_of(i) == "A"
            md = eng.store.metadata_of(i)
            assert md["shard"] == 1 and md["lang"] == "en"

    def test_isolation_survives_delete_and_compaction(self, backend):
        eng, db = make_engine(backend, compact_dead_frac=0.3)
        rng = np.random.default_rng(5)
        a = rng.normal(size=(30, D)).astype(np.float32)
        b = rng.normal(size=(30, D)).astype(np.float32)
        ids_a = eng.add_docs(a, tenant="A")
        eng.add_docs(b, tenant="B")
        # kill most of the tenantless pool and half of A, then force the
        # rebuild safe point — compaction remaps every surviving id
        eng.delete_docs(np.arange(0, 180))
        eng.delete_docs(ids_a[:15])
        eng.maybe_rebuild(force=True)
        assert eng.stats.n_compactions >= 1
        _, idx = eng.search(np.concatenate([a[15:19], b[:4]]), tenant="A")
        hit = [int(i) for i in idx.ravel() if i >= 0]
        assert hit
        for i in hit:
            assert eng.store.tenant_of(i) == "A"
        # the deleted half of A stays gone: its vectors no longer
        # self-retrieve exactly
        _, idx = eng.search(a[:4], tenant="A")
        for i in idx.ravel():
            if i >= 0:
                assert eng.store.tenant_of(int(i)) == "A"
