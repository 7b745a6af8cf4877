"""``correct`` comes out false when the timed path is broken underneath,
and when the control stands in the program's place."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import ROOT, tiny_cell
from harness import cell
from harness.faults import PLANTED


@pytest.mark.parametrize("fault", ["alter_an_answer", "drop_half_the_batch"])
@pytest.mark.parametrize("name", ["tiny-flat.tiny-closed",
                                  "tiny-ivf8.tiny-poisson",
                                  "tiny-flat-replicas2.tiny-closed"])
def test_a_broken_path_is_not_correct(tiny_root, name, fault):
    r = cell.run(tiny_cell(tiny_root, name), 4, 2.0, False,
                 t_start=time.monotonic(), require_tpu=False,
                 tamper=PLANTED[fault])
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("fault", ["alter_an_answer", "drop_half_the_batch"])
def test_a_broken_replica_behind_the_router_is_not_correct(tiny_root, fault):
    # planted in replica 1, a process of its own: the answers of every
    # replica are compared, not only those of the harness's own
    r = cell.run(tiny_cell(tiny_root, "tiny-flat-replicas2.tiny-closed"), 4,
                 2.0, False, t_start=time.monotonic(), require_tpu=False,
                 fault=fault)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("fault", ["probe_one_list", "wrong_lists"])
def test_a_broken_ivf_stage0_is_not_correct(tiny_root, fault):
    r = cell.run(tiny_cell(tiny_root, "tiny-ivf8.tiny-poisson"), 1, 2.0,
                 False, t_start=time.monotonic(), require_tpu=False,
                 tamper=PLANTED[fault])
    assert r["correct"] is False
    miss = r["checks"]["source_miss"]
    assert miss["value"] > miss["limit"]


def test_a_sound_path_is_correct(tiny_root):
    r = cell.run(tiny_cell(tiny_root, "tiny-flat.tiny-closed"), 4, 2.0,
                 False, t_start=time.monotonic(), require_tpu=False)
    assert r["correct"] is True


def control(tiny_root, name, *blocks):
    args = [a for b in blocks for a in ("--as", json.dumps(b))]
    p = subprocess.run(
        [sys.executable, os.path.join(tiny_root, "bench", "control.py"),
         "--workload", name, "--seeds", "11,12,13", "--cpu", *args],
        cwd=tiny_root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=os.path.join(ROOT, "src")))
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    assert len(rows) == 3 * max(len(blocks), 1), p.stderr[-3000:]
    return p.returncode, rows


@pytest.mark.parametrize("name", ["tiny-flat.tiny-poisson",
                                  "tiny-ivf8.tiny-poisson"])
def test_the_bfloat16_control_is_refused(tiny_root, name):
    rc, rows = control(tiny_root, name)
    assert all(r["refused"] for r in rows), rows
    assert rc == 0


def test_a_stage0_alone_on_a_4bit_grid_is_refused(tiny_root):
    rc, rows = control(tiny_root, "tiny-flat.tiny-poisson",
                       {"stage0_bits": 4})
    assert all(r["refused"] for r in rows), rows
    miss = [r["checks"]["miss_share"] for r in rows]
    assert all(m["value"] > m["limit"] for m in miss)
    assert rc == 0
