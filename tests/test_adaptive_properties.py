"""Hypothesis property: a cached query result is never served across a
``store_generation`` / ``mask_epoch`` / rebuild bump — for every backend
variant.

Add/delete sequences drive the engine's own lifecycle (tombstone
compaction past ``compact_dead_frac``, background/sync index rebuilds
past ``min_rebuild_rows``), so the three stamp components all move during
a run; the invariant is that a retrieve served with ``cached=True``
implies the stamp has not moved since the entry was inserted.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st

from repro.engine import CacheConfig, EngineDriver

from test_adaptive import BACKENDS, D, RNG, make_engine

_OPS = st.lists(
    st.sampled_from(["add", "delete", "hot", "hot", "fresh"]),
    min_size=3, max_size=10)

# One driver per backend shared across examples (construction + warm
# compilation dominate; the invariant is a safety property over any
# starting state, so carried-over corpus contents are fine).  The cache
# carries over with the driver, so the hot query's last uncached serve
# (ids, stamp right after it) carries over with it.
_DRIVERS = {}
_LAST_HOT = {}


def _shared_driver(backend):
    if backend not in _DRIVERS:
        eng, _ = make_engine(
            backend, n_docs=48,
            cache=CacheConfig(enabled=True, capacity=32))
        _DRIVERS[backend] = EngineDriver(eng, max_wait_ms=0.0).start()
    return _DRIVERS[backend]


@pytest.fixture(scope="module", autouse=True)
def _stop_shared_drivers():
    yield
    while _DRIVERS:
        _DRIVERS.popitem()[1].stop()
    _LAST_HOT.clear()


class TestCacheNeverStale:
    HOT = np.random.default_rng(99).normal(size=D).astype(np.float32)

    @pytest.mark.parametrize("backend", BACKENDS)
    @settings(max_examples=5, deadline=None)
    @given(ops=_OPS)
    def test_mutations_always_invalidate(self, backend, ops):
        """Interleave add/delete (which trigger compaction and rebuilds
        through the engine's own lifecycle) with hot-query retrieves; a
        cached serve must imply zero stamp movement since its insert."""
        drv = _shared_driver(backend)
        eng = drv.engine
        # ids from the last uncached hot serve and the stamp right after it
        last_ids, last_stamp = _LAST_HOT.get(backend, (None, None))
        for op in ops:
            if op == "add":
                eng.add_docs(RNG.normal(size=(2, D)).astype(np.float32))
            elif op == "delete":
                _, ids = eng.search(self.HOT[None, :], k=1)
                eng.delete_docs([int(ids[0, 0])])
            elif op == "fresh":
                q = RNG.normal(size=D).astype(np.float32)
                drv.retrieve(q, timeout=60)
            else:  # hot
                stamp_before = eng.cache_stamp()
                r = drv.retrieve(self.HOT, timeout=60)
                if r.cached:
                    # served from cache => nothing moved since insert
                    assert last_stamp is not None
                    assert stamp_before == last_stamp, (
                        "cached result served across a stamp bump")
                    np.testing.assert_array_equal(r.doc_ids, last_ids)
                    assert r.store_generation == eng.store.generation
                else:
                    last_ids = r.doc_ids
                    last_stamp = eng.cache_stamp()
                    _LAST_HOT[backend] = (last_ids, last_stamp)
