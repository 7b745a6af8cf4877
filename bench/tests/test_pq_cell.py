"""The PQ configuration's cell rehearsed on the CPU at a tiny size, the
faults its ``correct`` has to refuse, and the PQ kernel's roofline reader.

``tiny-pq`` stands in for ``gte3584-pq``: the quantized backend with PQ
codes of the stage-0 prefix, on the fused ``pq_scan`` kernel in interpret
mode.  Its cell is added to a copy of the tiny checkout as new files and
new BENCHMARK.json entries only.
"""

import json
import os
import shutil
import subprocess
import sys
import time
import types

import jax.numpy as jnp
import pytest

from conftest import ROOT, tiny_cell
from harness import cell, kernels, spec
from harness.faults import PLANTED

CELL = "tiny-pq.tiny-poisson"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def pq_root(tiny_root, tmp_path_factory):
    """The tiny checkout with the tiny PQ cell added, reporting what the
    accepted benchmark lists for ``gte3584-pq.poisson``."""
    root = str(tmp_path_factory.mktemp("pq-checkout"))
    shutil.copytree(tiny_root, root, dirs_exist_ok=True)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-pq", "source": "test fixture", "reduced": [],
        "file": "bench/configs/tiny-pq.json", "why": "test fixture"})
    bench["workloads"].append({"name": CELL, "config": "tiny-pq",
                               "traffic": "tiny-poisson", "chips": 1,
                               "why": "test fixture"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gte3584-pq.poisson" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return root


DRIVE = """
import json, sys, time
t = time.monotonic()
sys.path[:0] = [{bench!r}, {src!r}]
from harness import cell, spec
r = cell.run(spec.load_cell({name!r}), 5, 2.0, {trace}, t_start=t,
             require_tpu=False)
print(json.dumps(r))
"""


@pytest.mark.parametrize("trace", [False, True])
def test_the_tiny_pq_cell_runs_correct(pq_root, trace):
    code = DRIVE.format(bench=os.path.join(pq_root, "bench"),
                        src=os.path.join(ROOT, "src"), name=CELL, trace=trace)
    p = subprocess.run([sys.executable, "-c", code], cwd=pq_root,
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True, r["checks"]
    assert set(r["checks"]) == {"failed", "bad_answers", "source_miss",
                                "score_gap"}
    assert r["attempted"] > 0 and r["failed"] == 0
    want = ({"http_self_ms.poisson", "queue_wait_ms.poisson",
             "dispatch_ms.poisson", "device_idle.poisson"} if trace
            else {"search_p50_ms", "recall_at_10", "setup_s"})
    assert want <= set(r["metrics"])
    # the PQ cell's p95 spreads past half its bound on one chip, so the
    # cell does not report it
    assert "search_p95_ms" not in r["metrics"]
    # a CPU run has no peaks, so no roofline share
    assert "pq_scan_roofline" not in r["metrics"]


def wrong_codes(engine):
    """PQ stage 0 scores every row by the codes of a row half the corpus
    away."""
    idx = engine.index_state.data["idx"]
    codes = idx["codes"]
    idx["codes"] = jnp.roll(codes, codes.shape[0] // 2, axis=0)
    return lambda: idx.update(codes=codes)


@pytest.mark.parametrize("fault", [PLANTED["alter_an_answer"],
                                   PLANTED["drop_half_the_batch"],
                                   wrong_codes],
                         ids=lambda f: f.__name__)
def test_a_broken_pq_path_is_not_correct(pq_root, fault):
    r = cell.run(tiny_cell(pq_root, CELL), 6, 2.0, False,
                 t_start=time.monotonic(), require_tpu=False, tamper=fault)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
    if fault is wrong_codes:
        miss = r["checks"]["source_miss"]
        assert miss["value"] > miss["limit"]


def test_the_pq_control_is_refused(pq_root):
    p = subprocess.run(
        [sys.executable, os.path.join(pq_root, "bench", "control.py"),
         "--workload", CELL, "--seeds", "11,12,13", "--cpu"],
        cwd=pq_root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.path.join(ROOT, "src")))
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    assert len(rows) == 3, p.stderr[-3000:]
    assert all(r["refused"] for r in rows), rows
    assert p.returncode == 0


# -- pq_scan_roofline ----------------------------------------------------------

ROOFLINE = spec.reader("pq_scan_roofline")
COST = ROOFLINE.__globals__["pq_scan_cost"]
PQ = {"n_docs": 262144, "schedule": {"d_start": 128, "k0": 64},
      "engine": {"backend": {"backend": "quantized", "codec": "pq",
                             "pq_m": 32, "pq_codes": 256,
                             "pq_oversample": 4}}}


def roofline_ctx(calls, seconds, buckets, config=PQ):
    trace = types.SimpleNamespace(op_time=lambda *p: (calls, seconds))
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(config=config), trace=trace,
        traced={"bucket_counts": buckets}, peaks=PEAKS)


def test_the_pq_scan_cost_counts_codes_once_a_dispatch():
    cost = COST(n_docs=262144, m=32, codes=256, k=256, bucket=4)
    assert cost["bytes"] == 262144 * 36 + 4 * 32 * 256 * 4 + 4 * 8 * 256
    assert cost["flops"] == 2 * 4 * 262144 * 32
    assert kernels.least_seconds(cost, PEAKS)[1] == "memory"


@pytest.mark.parametrize("buckets", [{1: 3}, {2: 1, 4: 1}])
def test_the_roofline_is_100_at_the_least_time(buckets):
    calls = sum(buckets.values())
    mean = sum(b * n for b, n in buckets.items()) / calls
    least = calls * kernels.least_seconds(COST(
        n_docs=262144, m=32, codes=256, k=256, bucket=mean), PEAKS)[0]
    assert ROOFLINE(roofline_ctx(calls, least, buckets)) == \
        pytest.approx(100.0)
    assert ROOFLINE(roofline_ctx(calls, 4 * least, buckets)) == \
        pytest.approx(25.0)


def test_the_roofline_is_none_without_kernel_calls():
    assert ROOFLINE(roofline_ctx(0, 0.0, {1: 3})) is None
    flat = dict(PQ, engine={"backend": {"backend": "flat"}})
    assert ROOFLINE(roofline_ctx(3, 1e-3, {1: 3}, flat)) is None
