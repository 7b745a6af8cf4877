"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state, so smoke tests / benches keep seeing the single real CPU
device.  Only launch/dryrun.py (which sets XLA_FLAGS before any jax import)
ever asks for the 256/512-device meshes.

Topology: one TPU v5e pod = 16 x 16 chips -> axes ('data', 'model');
multi-pod = 2 pods -> ('pod', 'data', 'model') with the pod axis crossing
DCN.  Sharding rules map logical axes onto these names
(`repro.sharding.specs`), so the same model code lowers on any of them.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: shardings follow the
    annotations (``jax.make_mesh`` alone makes Explicit axes)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_elastic_mesh(n_model: int = 0):
    """Build the largest (data, model) mesh the *currently healthy* device
    set supports — the elastic-rescale entry point: after a node failure the
    job restarts, sees fewer devices, and trains on (n_live // n_model,
    n_model) with the same logical sharding rules.
    """
    devs = jax.devices()
    n = len(devs)
    if n_model <= 0:
        n_model = min(16, n)
    while n_model > 1 and n % n_model:
        n_model //= 2
    n_data = n // n_model
    return make_mesh((n_data, n_model), ("data", "model"))
