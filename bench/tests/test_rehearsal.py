"""CPU rehearsal of the whole harness at tiny fixture configurations.

The tiny cells are added to a copy of the benchmark's files as new files and
new BENCHMARK.json entries only, and run from there with the look for a
chip skipped; the real command must refuse to run without a TPU.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

RUN = [sys.executable, os.path.join("bench", "run.py")]


def run_cmd(args, cwd, **env):
    return subprocess.run(RUN + args, cwd=cwd, capture_output=True,
                          text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu", **env))


def test_without_a_tpu_there_is_no_result():
    p = run_cmd(["--workload", "gte3584-flat.poisson", "--seed", "1",
                 "--seconds", "5", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not p.stdout.strip()


def test_outside_a_checkout_there_is_no_result(tmp_path):
    os.symlink(BENCH, tmp_path / "bench")
    (tmp_path / "BENCHMARK.json").write_text(
        open(os.path.join(ROOT, "BENCHMARK.json")).read())
    p = run_cmd(["--workload", "gte3584-flat.poisson", "--seed", "1",
                 "--seconds", "5", "--trace", "0"], str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()


DRIVE = """
import json, sys, time
t = time.monotonic()
sys.path[:0] = [{bench!r}, {src!r}]
from harness import cell, spec
r = cell.run(spec.load_cell({name!r}), {seed}, {seconds}, {trace},
             t_start=t, require_tpu=False)
print(json.dumps(r))
"""


@pytest.mark.parametrize("name,trace", [
    ("tiny-flat.tiny-poisson", False), ("tiny-flat.tiny-closed", True),
    ("tiny-ivf8.tiny-poisson", False),
    ("tiny-flat-replicas2.tiny-closed", False),
    ("tiny-flat-replicas2.tiny-closed", True)])
def test_cells_added_as_files_run(tiny_root, name, trace):
    code = DRIVE.format(bench=os.path.join(tiny_root, "bench"),
                        src=os.path.join(ROOT, "src"), name=name, seed=3,
                        seconds=2.0, trace=trace)
    p = subprocess.run([sys.executable, "-c", code], cwd=tiny_root,
                       capture_output=True, text=True, timeout=600,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    early, r = json.loads(lines[-2]), json.loads(lines[-1])
    assert early["platform"] == "cpu" and "lateness_p95_ms" in early
    assert r["correct"] is True, r["checks"]
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    replicas = 2 if "replicas2" in name else 1
    assert r["device"]["count"] == early["device_count"]
    metrics = r["metrics"]
    if replicas > 1:
        assert r["device"]["count"] == replicas
        assert ("replica_min_share.closed" in metrics) == trace
    if trace:
        assert {"http_self_ms.closed", "batch_fill.closed",
                "dispatch_ms.closed", "device_idle.closed"} <= set(metrics)
        assert r["device"]["window_s"] > 0 and "breakdown" in r
        if replicas > 1:            # every replica served through the router
            assert 0 < metrics["replica_min_share.closed"]["value"] <= 1
    else:
        want = {"recall_at_10", "setup_s"} | (
            {"search_p95_ms", "search_p50_ms"} if "poisson" in name
            else {"search_qps"})
        assert want <= set(metrics)
        assert 0 < metrics["recall_at_10"]["value"] <= 1
    assert "check failed:" in p.stderr.splitlines()[-len(r["checks"])]


@pytest.mark.parametrize("chips", [1, 4])
def test_a_cell_whose_chips_differ_from_its_replicas_is_refused(
        tiny_root, tmp_path, chips):
    from harness import spec

    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["config"] == "tiny-flat-replicas2":
            w["chips"] = chips
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match="replicas"):
        spec.load_cell("tiny-flat-replicas2.tiny-closed", root=str(tmp_path),
                       bench=os.path.join(tiny_root, "bench"))
