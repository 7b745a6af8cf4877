"""The PQ stage 0 served over HTTP against a plain reference.

A quantized engine with ``codec='pq'`` answers searches sent concurrently
through ``serve_in_thread`` -> ``EngineDriver`` -> ``RetrievalEngine``, on
the fused ``pq_scan`` kernel (interpret mode) and on the XLA ADC path.
`pq_reference.search`, given the engine's own codebooks and codes as data,
answers the same queries; both must return the same ids, and the served
scores must be the reference's full-dimension scores.  The cases cover the
built corpus, tombstones, and rows appended past the coded prefix, which
reach stage 0 through the tail window.
"""

import json
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import pq_reference
from repro.engine import (
    EngineConfig,
    EngineDriver,
    QuantizedConfig,
    RetrievalEngine,
)
from repro.serve import serve_in_thread

D, N_BUILT, N_TAIL, CAPACITY = 128, 1000, 24, 1024
D0, K0, FINAL_K, OVERSAMPLE = 16, 16, 10, 4
# the schedule 16 -> 128 with K 16, top-10: dims double, k halves, never
# below final_k
STAGES = ((16, 16), (32, 10), (64, 10), (128, 10))
RNG = np.random.default_rng(2024)
CENTRES = 3.0 * RNG.normal(size=(16, D)).astype(np.float32)


def rows(n):
    topic = RNG.integers(0, len(CENTRES), n)
    return (CENTRES[topic] + RNG.normal(size=(n, D))).astype(np.float32)


def search_over_http(url, queries):
    def one(q):
        req = urllib.request.Request(
            url + "/v1/search", method="POST",
            data=json.dumps({"query": q.tolist(), "k": FINAL_K}).encode())
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            out = json.loads(resp.read())
        return out["ids"], out["scores"]

    # concurrent clients, so the driver forms batches of several buckets
    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(one, queries))
    return (np.array([i for i, _ in got], np.int64),
            np.array([s for _, s in got], np.float64))


@pytest.fixture(scope="module")
def corpus():
    built = rows(N_BUILT)
    tail = rows(N_TAIL)
    # noisy copies of built and appended rows, and fresh draws
    src = np.concatenate([RNG.choice(N_BUILT, 24, replace=False),
                          N_BUILT + np.arange(8)])
    db = np.concatenate([built, tail])
    copies = db[src] + 0.3 * RNG.normal(size=(src.size, D)).astype(np.float32)
    queries = np.concatenate([copies, rows(16)]).astype(np.float32)
    return built, tail, src, queries


@pytest.mark.parametrize("case", ["built", "tombstones", "tail"])
@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "xla"])
def test_served_pq_search_matches_the_reference(corpus, use_kernel, case):
    built, tail, src, queries = corpus
    eng = RetrievalEngine(config=EngineConfig(
        d_emb=D, d_start=D0, k0=K0, final_k=FINAL_K, buckets=(1, 2, 4, 8),
        capacity=CAPACITY,
        backend=QuantizedConfig(codec="pq", pq_m=4, pq_codes=32,
                                pq_oversample=OVERSAMPLE,
                                use_kernel=use_kernel,
                                # appended rows ride the tail window
                                encode_appends=False)))
    eng.add_docs(built)
    eng.maybe_rebuild(force=True)
    db, valid, tail_ids = built, np.ones(N_BUILT, bool), np.arange(0)
    if case == "tombstones":
        # the rows half the copies were made from, and a few others
        dead = np.unique(np.concatenate([src[:12], np.arange(0, 400, 37)]))
        assert eng.delete_docs(dead) == dead.size
        valid[dead] = False
    elif case == "tail":
        assert eng.add_docs(tail).tolist() == list(range(N_BUILT,
                                                          N_BUILT + N_TAIL))
        db = np.concatenate([built, tail])
        valid = np.ones(len(db), bool)
        tail_ids = np.arange(N_BUILT, N_BUILT + N_TAIL)
    state = eng.index_state
    idx = state.data["idx"]
    codes, codebooks = np.asarray(idx["codes"]), np.asarray(idx["codebooks"])

    with EngineDriver(eng, max_wait_ms=2.0) as driver:
        handle = serve_in_thread(eng, driver, require_tenant=False)
        try:
            ids, scores = search_over_http(handle.url, queries)
        finally:
            handle.stop()
    # no rebuild happened: the state the reference was handed served
    assert eng.index_state is state
    assert state.data["coded_upto"] == N_BUILT

    want_s, want_i = pq_reference.search(
        queries, db, codes, codebooks, STAGES, valid=valid, coded=N_BUILT,
        tail=tail_ids, oversample=OVERSAMPLE)
    for got, want in zip(ids, want_i):
        assert set(got.tolist()) == set(want.tolist())
    # served scores are the ladder's float32 full-dimension scores, the
    # reference's at HIGHEST: the two sum the same 128 products in
    # different orders, so they agree to float32 rounding of terms of
    # ||x||^2 + ||q||^2 (about 2,100 here); 1e-5 of that is ~100x the
    # rounding and far below the gap of any other row
    order = np.argsort(ids, axis=1)
    got_s = np.take_along_axis(scores, order, axis=1)
    want_s = np.take_along_axis(
        want_s, np.argsort(want_i, axis=1), axis=1)
    scale = np.sum(queries.astype(np.float64) ** 2, axis=1)[:, None] \
        + np.max(np.sum(db.astype(np.float64) ** 2, axis=1))
    assert np.all(np.abs(got_s - want_s) <= 1e-5 * scale)
    assert not np.isin(ids, np.flatnonzero(~valid)).any()
    if case == "tail":
        # appended rows are found from stage 0 through the tail window
        assert (ids[24:32] == src[24:32, None]).any(axis=1).all()
