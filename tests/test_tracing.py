"""The program's own measurement: profiler spans, named scopes, compiles.

A served search under a CPU ``jax.profiler`` trace shows every host span
the serving path names (``repro.http.*``, ``repro.driver.*``,
``repro.engine.*``); each backend's device program carries the ``stage0``
and ``rescore`` named scopes in its op metadata and still returns the
answers the program gave before the scopes were added; the compile log
counts real XLA compiles; spans cost nothing but a shared no-op when
observability is off.
"""

import glob
import json
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.ivf as core_ivf
import repro.core.pq as core_pq
import repro.index_backends.flat as flat_backend
import repro.index_backends.ivf as ivf_backend
import repro.index_backends.quantized as quantized_backend
import repro.obs.trace as obs_trace
from repro.engine import EngineConfig, EngineDriver, RetrievalEngine
from repro.engine.config import (
    FlatConfig,
    IVFConfig,
    ObsConfig,
    QuantizedConfig,
)
from repro.obs import COMPILES, NULL_SPAN, parse_prometheus, span
from repro.serve import serve_in_thread

D = 16

HOST_SPANS = (
    "repro.http.parse", "repro.http.respond", "repro.http.search",
    "repro.http.decode", "repro.http.encode",
    "repro.driver.idle", "repro.driver.hold", "repro.driver.execute",
    "repro.engine.rebuild", "repro.engine.mask", "repro.engine.enqueue",
    "repro.engine.sync", "repro.engine.fetch", "repro.engine.results",
)


def post(url, path, body):
    req = urllib.request.Request(
        url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def scrape(url):
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        return parse_prometheus(r.read().decode())


def served_engine(**obs):
    eng = RetrievalEngine(D, d_start=4, k0=8, final_k=4, buckets=(1, 2, 4),
                          capacity=64, block_n=64, obs=ObsConfig(**obs))
    db = np.random.default_rng(5).normal(size=(48, D)).astype(np.float32)
    eng.add_docs(db)
    eng.warmup()
    return eng, db


# -- host spans --------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_spans(tmp_path_factory):
    """Host span events of a CPU profile around searches served over HTTP:
    name -> [(line index, start_ns, duration_ns, args)]."""
    from jax.profiler import ProfileData

    eng, db = served_engine()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with EngineDriver(eng, max_wait_ms=2.0) as driver:
        handle = serve_in_thread(eng, driver, require_tenant=False)
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                for i in range(4):                  # one at a time: holds
                    post(handle.url, "/v1/search", {"query": db[i].tolist()})
                threads = [threading.Thread(target=post, args=(
                    handle.url, "/v1/search", {"query": db[i].tolist()}))
                    for i in range(8)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            finally:
                jax.profiler.stop_trace()
        finally:
            handle.stop()
    path, = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.setdefault(e.name, []).append(
                        (j, e.start_ns, e.duration_ns,
                         {str(k): str(v) for k, v in e.stats}))
    return out


@pytest.mark.parametrize("name", HOST_SPANS)
def test_served_search_shows_host_span(traced_spans, name):
    assert traced_spans.get(name), sorted(traced_spans)


def test_engine_spans_nest_in_the_driver_execute_span(traced_spans):
    execute = traced_spans["repro.driver.execute"]
    for name in ("repro.engine.enqueue", "repro.engine.sync",
                 "repro.engine.fetch", "repro.engine.results"):
        for line, start, dur, _ in traced_spans[name]:
            assert any(ln == line and s <= start and start + dur <= s + d
                       for ln, s, d, _ in execute), name


def test_span_args_name_the_batch(traced_spans):
    for _, _, _, args in traced_spans["repro.driver.execute"]:
        assert int(args["bucket"]) >= int(args["fill"]) >= 1
    for _, _, _, args in traced_spans["repro.engine.enqueue"]:
        assert int(args["bucket"]) in (1, 2, 4)
    waits = [float(a["executor_wait_ms"])
             for _, _, _, a in traced_spans["repro.http.search"]]
    assert len(waits) == 12 and min(waits) >= 0.0


# -- observability off -------------------------------------------------------

def test_disabled_span_is_the_shared_noop():
    assert span("engine.sync", False) is NULL_SPAN
    assert span("engine.sync", False, bucket=4) is NULL_SPAN
    assert span("engine.sync") is not NULL_SPAN


@pytest.mark.parametrize("enabled", [False, True])
def test_served_search_opens_spans_only_when_enabled(monkeypatch, enabled):
    opened = []

    def annotation(name, **args):
        opened.append(name)
        return NULL_SPAN

    monkeypatch.setattr(obs_trace, "TraceAnnotation", annotation)
    eng, db = served_engine(enabled=enabled)
    with EngineDriver(eng, max_wait_ms=1.0) as driver:
        handle = serve_in_thread(eng, driver, require_tenant=False)
        try:
            assert post(handle.url, "/v1/search",
                        {"query": db[3].tolist()})["ids"][0] == 3
        finally:
            handle.stop()
    assert bool(opened) is enabled
    if enabled:
        assert {"repro.http.search", "repro.engine.enqueue"} <= set(opened)


# -- executor wait -----------------------------------------------------------

def test_executor_wait_counts_one_observation_per_search():
    eng, db = served_engine()
    with EngineDriver(eng, max_wait_ms=1.0) as driver:
        handle = serve_in_thread(eng, driver, require_tenant=False)
        try:
            for i in range(5):
                post(handle.url, "/v1/search", {"query": db[i].tolist()})
            urllib.request.urlopen(handle.url + "/healthz", timeout=30).read()
            samples = scrape(handle.url)
        finally:
            handle.stop()
    assert samples["repro_http_executor_wait_ms_count"] == {(): 5.0}


# -- named scopes ------------------------------------------------------------

RNG = np.random.default_rng(1234)
DB = RNG.normal(size=(512, 64)).astype(np.float32)
QUERIES = DB[:6] + 0.05 * RNG.normal(size=(6, 64)).astype(np.float32)

# (backend config, the jitted program it dispatches, the scopes its op
# metadata must carry, the top-5 ids the program returned for QUERIES
# before the scopes were added)
PROGRAMS = {
    "flat": (
        FlatConfig(), (flat_backend, "progressive_search"),
        ("stage0", "rescore"),
        [[0, 367, 439, 392, 383], [1, 437, 231, 47, 340],
         [2, 373, 101, 325, 243], [3, 79, 510, 233, 476],
         [4, 335, 328, 230, 438], [5, 395, 204, 229, 58]]),
    "ivf_int8_kernel": (
        IVFConfig(n_lists=8, n_probe=3, stage0_dtype="int8", use_kernel=True,
                  min_index_rows=16),
        (core_ivf, "_kernel_search_jit"),
        ("stage0", "stage0/probe", "stage0/member_mask", "stage0/scan",
         "stage0/tail", "rescore"),
        [[0, 367, 439, 508, 392], [1, 437, 231, 47, 340],
         [2, 242, 367, 158, 301], [3, 79, 510, 221, 360],
         [4, 328, 438, 124, 479], [5, 204, 325, 229, 235]]),
    "ivf_xla": (
        IVFConfig(n_lists=8, n_probe=3, use_kernel=False, min_index_rows=16),
        (ivf_backend, "ivf_progressive_search_sched"),
        ("stage0", "stage0/probe", "rescore"),
        [[0, 367, 439, 508, 392], [1, 437, 231, 47, 340],
         [2, 242, 158, 301, 101], [3, 79, 510, 221, 360],
         [4, 328, 438, 124, 479], [5, 204, 325, 229, 235]]),
    "quantized_int8": (
        QuantizedConfig(), (quantized_backend, "quantized_progressive_search"),
        ("stage0", "rescore"),
        [[0, 367, 439, 392, 383], [1, 437, 231, 47, 340],
         [2, 373, 101, 325, 243], [3, 79, 510, 233, 476],
         [4, 335, 328, 230, 438], [5, 395, 204, 229, 58]]),
    "quantized_pq": (
        QuantizedConfig(codec="pq", pq_m=4, pq_codes=16, use_kernel=True),
        (core_pq, "pq_progressive_search_kernel"),
        ("stage0", "stage0/lut", "stage0/scan", "stage0/tail", "rescore"),
        [[0, 439, 508, 392, 383], [1, 437, 231, 47, 340],
         [2, 367, 103, 41, 325], [3, 79, 510, 360, 166],
         [4, 335, 328, 230, 438], [5, 373, 395, 204, 229]]),
}


@pytest.fixture(scope="module")
def searched():
    """Per backend: (the lowered program's text with debug locations, the
    ids ``engine.search`` returned for QUERIES)."""
    out = {}
    mp = pytest.MonkeyPatch()
    try:
        for name, (cfg, (module, attr), _, _) in PROGRAMS.items():
            texts = []
            fn = getattr(module, attr)

            def lowered(*a, _fn=fn, _texts=texts, **k):
                _texts.append(_fn.lower(*a, **k).as_text(debug_info=True))
                return _fn(*a, **k)

            mp.setattr(module, attr, lowered)
            eng = RetrievalEngine(config=EngineConfig(
                d_emb=64, d_start=16, k0=16, final_k=5, buckets=(1, 2, 4, 8),
                capacity=512, backend=cfg))
            eng.add_docs(DB)
            eng.maybe_rebuild(force=True)
            _, ids = eng.search(QUERIES)
            mp.undo()
            assert texts, f"{name} did not dispatch {attr}"
            out[name] = (texts[-1], ids.tolist())
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("name", PROGRAMS)
def test_program_carries_its_named_scopes(searched, name):
    text, _ = searched[name]
    for scope in PROGRAMS[name][2]:
        assert f"/{scope}/" in text, scope
    # each operation is counted under one of the two
    assert "/stage0/rescore/" not in text
    assert "/rescore/stage0/" not in text


@pytest.mark.parametrize("name", PROGRAMS)
def test_scoped_program_answers_as_before(searched, name):
    assert searched[name][1] == PROGRAMS[name][3]


# -- compile log -------------------------------------------------------------

def test_compile_log_records_a_fresh_shape_once():
    def fresh_program_for_the_log(x):
        return jnp.sin(x) * 3.0 + 1.0

    f = jax.jit(fresh_program_for_the_log)
    x = jnp.ones((7, 5), jnp.float32)
    n0 = COMPILES.totals()[0]
    f(x).block_until_ready()
    n1 = COMPILES.totals()[0]
    ours = [e for e in COMPILES.events()
            if "fresh_program_for_the_log" in e[2]]
    assert n1 > n0
    assert len(ours) == 1 and ours[0][1] > 0
    f(x).block_until_ready()
    assert COMPILES.totals()[0] == n1
    assert len([e for e in COMPILES.events()
                if "fresh_program_for_the_log" in e[2]]) == 1


def test_every_engine_exports_the_process_compile_totals():
    eng, _ = served_engine()
    eng.search(np.zeros((1, D), np.float32))
    text = eng.metrics.render_prometheus()
    samples = parse_prometheus(text)
    n, seconds, hits = COMPILES.totals()
    assert samples["repro_jax_compiles_total"][()] == n > 0
    assert samples["repro_jax_compile_seconds_total"][()] == \
        pytest.approx(seconds)
    assert samples["repro_jax_compile_cache_hits_total"][()] == hits
