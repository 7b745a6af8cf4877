"""CPU tests of the benchmark harness (run: ``python -m pytest bench/tests``).

The harness's imports resolve against this checkout's ``bench/`` and
``src/``; JAX is held to the CPU, where the Pallas kernel runs in interpret
mode.
"""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
os.environ["JAX_PLATFORMS"] = "cpu"

# the tiny cells a temporary checkout adds as files
TINY = {
    "configs": ["tiny-flat", "tiny-ivf8", "tiny-flat-replicas2"],
    "workloads": [("tiny-flat.tiny-poisson", "tiny-flat", "tiny-poisson"),
                  ("tiny-ivf8.tiny-poisson", "tiny-ivf8", "tiny-poisson"),
                  ("tiny-flat.tiny-closed", "tiny-flat", "tiny-closed"),
                  ("tiny-flat-replicas2.tiny-closed", "tiny-flat-replicas2",
                   "tiny-closed")],
}


def add_tiny_cells(root: str) -> None:
    """Add the tiny configurations, mixes and cells to the checkout at
    ``root`` as new files and new BENCHMARK.json entries only."""
    for kind in ("configs", "traffic"):
        for name in os.listdir(os.path.join(FIXTURES, kind)):
            dst = os.path.join(root, "bench", kind, name)
            assert not os.path.exists(dst), f"{dst} would be edited"
            shutil.copy(os.path.join(FIXTURES, kind, name), dst)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for name in TINY["configs"]:
        spec["configs"].append({
            "name": name, "source": "test fixture", "reduced": [],
            "file": f"bench/configs/{name}.json", "why": "test fixture"})
    for name, config, traffic in TINY["workloads"]:
        with open(os.path.join(FIXTURES, "configs", f"{config}.json")) as f:
            chips = json.load(f).get("replicas", 1)
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": chips,
                                  "why": "test fixture"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            mix = "poisson" if "poisson" in traffic else "closed"
            reads = m["name"].split(".")[-1] if "." in m["name"] else None
            uses = {"search_p95_ms": "poisson", "search_p50_ms": "poisson",
                    "search_qps": "closed"}.get(m["name"], reads)
            if "workloads" in m and uses in (mix, None) \
                    and m["name"] != "ivf_scan_roofline" \
                    and (chips > 1 or not m["name"].startswith("replica_")):
                m["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(spec, f, indent=1)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A copy of the benchmark's files with the tiny cells added."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    add_tiny_cells(root)
    return root


def tiny_cell(root: str, name: str):
    from harness import spec

    return spec.load_cell(name, root=root, bench=os.path.join(root, "bench"))
