"""One fixed home for JAX's persistent compilation cache.

Entry points that run on a chip (``chip_smoke.py``, ``repro.launch.serve``,
the benchmark mains) call `enable_compile_cache` once, before their first
compile, so a second process of the same checkout reuses the first one's
compiled programs.  Library code and tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root (src/repro/launch/ -> three levels up); git-ignored
CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache is `CACHE_DIR`, one path that
    never moves, so every process of the checkout finds what an earlier
    one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
