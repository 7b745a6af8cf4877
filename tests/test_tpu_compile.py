"""The serving path's device programs compile for a TPU v5e, without a chip.

Each test lowers one program the engine dispatches at the paper cell's
widths (3584-dim rows, Table III schedule 128 -> 3584 with K 64, final_k 10,
262,144 stored rows; stage-0 dim 128 and 128-row kernel blocks) and
compiles it against a described ``v5e:2x2`` topology, so Mosaic and XLA
refuse here what they would refuse on the chip: block shapes off the
(8, 128) tiling, primitives Pallas cannot lower, programs that do not fit
the device.  Where a fused kernel is expected, the compiled HLO must hold a
``tpu_custom_call``.  Nothing runs, so nothing here says anything about
results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU runtime, and every test worker imports
this file.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import make_schedule
from repro.core.progressive import progressive_search

DIM, N_ROWS, Q = 3584, 262_144, 1
SCHED = make_schedule(128, DIM, 64, final_k=10)
DIMS = tuple(s.dim for s in SCHED.stages)
D0 = SCHED.stages[0].dim
K0 = SCHED.stages[0].k
BLOCK_M = 128
N_LISTS, MAX_LEN, N_PROBE = 2048, 512, 12     # the IVF build at N_ROWS
PQ_M, PQ_C, PQ_OVERSAMPLE = 32, 256, 4        # auto_pq_m(128), 256 codes
TAIL = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(sharding, **shapes):
    return {name: (None if spec is None else
                   jax.ShapeDtypeStruct(spec[0], spec[1], sharding=sharding))
            for name, spec in shapes.items()}


def _store(sharding, nq=Q):
    return _shapes(
        sharding,
        q=((nq, DIM), jnp.float32),
        db=((N_ROWS, DIM), jnp.float32),
        valid=((N_ROWS,), jnp.bool_),
        sq_prefix=((N_ROWS, len(DIMS)), jnp.float32),
        tail=((TAIL,), jnp.int32),
    )


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_flat_progressive_search_compiles(one_chip):
    s = _store(one_chip)
    compiled = progressive_search.lower(
        s["q"], s["db"], SCHED, sq_prefix=s["sq_prefix"], index_dims=DIMS,
        valid=s["valid"], block_n=65536).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= N_ROWS * DIM * 4


def _compile_ivf_search(sharding, dtype, nq):
    """`_kernel_search_jit` compiled at ``nq`` queries; its HLO text."""
    from repro.core.ivf import _kernel_search_jit

    s = _store(sharding, nq)
    slots = N_LISTS * MAX_LEN
    pq = dtype == "pq"
    p = _shapes(
        sharding,
        centroids=((N_LISTS, DIM), jnp.float32),
        lists=((N_LISTS, MAX_LEN), jnp.int32),
        cent_sq=((N_LISTS,), jnp.float32),
        rows=((slots, PQ_M if pq else D0),
              {"float32": jnp.float32, "int8": jnp.int8,
               "pq": jnp.uint8}[dtype]),
        sq=None if pq else ((N_LISTS, MAX_LEN), jnp.float32),
        scale=((D0,), jnp.float32) if dtype == "int8" else None,
        codebooks=((PQ_M, PQ_C, D0 // PQ_M), jnp.float32) if pq else None,
        pack_cent_sq=((PQ_M, PQ_C), jnp.float32) if pq else None,
    )
    compiled = _kernel_search_jit.lower(
        s["q"], s["db"], p["centroids"], p["lists"], p["rows"], p["sq"],
        p["scale"], p["codebooks"], p["pack_cent_sq"], s["valid"],
        s["sq_prefix"], s["tail"], p["cent_sq"], SCHED,
        n_probe=N_PROBE, index_dims=DIMS, metric="l2",
        pack_meta=(D0, MAX_LEN, BLOCK_M, dtype),
        pq_oversample=PQ_OVERSAMPLE if pq else 1, interpret=False,
    ).compile()
    assert _kernel_calls(compiled) >= 1
    text = compiled.as_text()
    # the device trace names the kernel by its HLO instruction (the
    # benchmark's ivf_scan_roofline matches it) and files it under the
    # program's stage0/scan named scope
    kernel = "%_ivf_scan_call" if not pq else "%_pq_ivf_call"
    lines = [ln for ln in text.splitlines() if kernel in ln.split("=")[0]]
    assert lines and all("/stage0/scan/" in ln for ln in lines)
    return text


def _member_mask_sizes(text):
    """Element counts of every HLO value computed under
    ``stage0/member_mask`` (fused computations' bodies included)."""
    sizes = []
    for ln in text.splitlines():
        if "/stage0/member_mask/" not in ln:
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%\S+\s*=\s*(.*?)\s+[\w-]+\(", ln)
        for dims in re.findall(r"\[([\d,]*)\]", m.group(1) if m else ""):
            sizes.append(math.prod(int(x) for x in dims.split(",") if x))
    return sizes


@pytest.mark.parametrize("dtype", ["float32", "int8", "pq"])
def test_ivf_kernel_search_compiles(one_chip, dtype):
    """`ivf_progressive_search_kernel`'s program: probe, fused stage-0
    (`ivf_scan` f32/int8, or `pq_scan`'s list-major scan), tail merge and
    the rescore ladder.  With Q·n_probe under n_lists the validity gather
    covers the probed slots alone: nothing under ``stage0/member_mask`` is
    the size of the member table."""
    assert Q * N_PROBE < N_LISTS
    sizes = _member_mask_sizes(_compile_ivf_search(one_chip, dtype, Q))
    assert Q * N_PROBE * MAX_LEN in sizes
    assert N_LISTS * MAX_LEN not in sizes
    assert max(sizes) < N_LISTS * MAX_LEN


@pytest.mark.parametrize("dtype", ["float32", "int8", "pq"])
def test_ivf_wide_probes_mask_the_member_table_once(one_chip, dtype):
    """At Q·n_probe ≥ n_lists masking per query would gather more than the
    member table: the program masks the whole table once, then gathers
    the probed rows into the same per-query layout."""
    nq = 256
    assert nq * N_PROBE >= N_LISTS
    sizes = _member_mask_sizes(_compile_ivf_search(one_chip, dtype, nq))
    assert N_LISTS * MAX_LEN in sizes
    assert nq * N_PROBE * MAX_LEN in sizes


@pytest.mark.parametrize("pq_m", [PQ_M, PQ_M // 2])
def test_pq_flat_kernel_search_compiles(one_chip, pq_m):
    """`pq_progressive_search_kernel`'s program: LUTs, the flat fused
    `pq_scan` over every coded row, tail injection and the ladder, at the
    automatic 4-dim subspaces and at 8-dim ones."""
    from repro.core.pq import pq_progressive_search_kernel

    s = _store(one_chip)
    idx = _shapes(
        one_chip,
        db=((N_ROWS, DIM), jnp.float32),
        codes=((N_ROWS, pq_m), jnp.uint8),
        codebooks=((pq_m, PQ_C, D0 // pq_m), jnp.float32),
        cent_sq=((pq_m, PQ_C), jnp.float32),
    )
    limit = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = pq_progressive_search_kernel.lower(
        s["q"], idx, SCHED, db=s["db"], valid=s["valid"], row_limit=limit,
        extra_cand=s["tail"], block_m=BLOCK_M, oversample=PQ_OVERSAMPLE,
        interpret=False,
    ).compile()
    assert _kernel_calls(compiled) >= 1
    text = compiled.as_text()
    # the benchmark's pq_scan_roofline finds the kernel by this HLO name,
    # and stage0_device_ms its work by the stage0 sub-scopes
    lines = [ln for ln in text.splitlines()
             if "%_pq_scan_call" in ln.split("=")[0]]
    assert lines and all("/stage0/scan/" in ln for ln in lines)
    for scope in ("/stage0/lut/", "/stage0/scan/", "/stage0/tail/"):
        assert scope in text, scope


@pytest.mark.parametrize("block_q", [8, 64])
def test_l2_topk_kernel_compiles(one_chip, block_q):
    """The blocked distance + top-k kernel: the running merge over several
    query rows at once."""
    from repro.kernels.distance_topk import l2_topk

    qs, dbs = _shapes(one_chip, q=((block_q, D0), jnp.float32),
                      db=((N_ROWS, D0), jnp.float32)).values()
    compiled = l2_topk.lower(qs, dbs, k=K0, block_q=block_q,
                             block_n=512).compile()
    assert _kernel_calls(compiled) >= 1
