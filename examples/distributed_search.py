"""Distributed corpus-sharded progressive search across 8 (simulated)
devices — the multi-node serving layout in miniature.

    PYTHONPATH=src python examples/distributed_search.py

The corpus shards row-wise over the 'data' mesh axis; each shard runs the
full progressive pipeline locally and only (score, index) pairs cross the
interconnect (see repro/core/distributed.py for why recall is preserved).
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.core import (build_index, make_schedule, progressive_search,
                        sharded_progressive_search, stage_dims,
                        top1_accuracy)
from repro.rag import make_corpus


def main():
    print(f"devices: {len(jax.devices())}")
    c = make_corpus(n_docs=40_000, dim=256, n_queries=200, seed=0)
    db, q = jnp.asarray(c.db), jnp.asarray(c.queries)
    gt = jnp.asarray(c.ground_truth)
    sched = make_schedule(64, 256, 128)
    idx = build_index(db, stage_dims(sched))

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    for mode in ("local", "global"):
        t0 = time.perf_counter()
        s, i = sharded_progressive_search(
            mesh, q, db, sched, sq_prefix=idx["sq_prefix"],
            index_dims=stage_dims(sched), block_n=5000, mode=mode)
        jax.block_until_ready(i)
        dt = time.perf_counter() - t0
        acc = float(top1_accuracy(i, gt)) * 100
        print(f"sharded[{mode:6s}]: acc={acc:.2f}%  wall={dt*1e3:.0f}ms")

    _, i1 = progressive_search(q, db, sched, sq_prefix=idx["sq_prefix"],
                               index_dims=stage_dims(sched))
    print(f"single-device   : acc={float(top1_accuracy(i1, gt))*100:.2f}%")
    print("OK")


if __name__ == "__main__":
    main()
