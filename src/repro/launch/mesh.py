"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax import
and only then calls make_production_mesh().

Mesh geometry (TPU v5e pods of 256 chips):
  single-pod: (16, 16)            axes (data, model)
  multi-pod:  (2, 16, 16)         axes (pod, data, model)

The "model" axis carries TP/EP/sequence sharding (high-bandwidth inner ICI
ring); "data"/"pod" carry data parallelism (gradient all-reduce tolerates the
lower-bandwidth cross-pod links — DCN between pods in a real deployment).
"""
from __future__ import annotations

import jax

# the sharding rules place arrays themselves; explicit-typed axes (JAX's
# make_mesh default) would type every array's sharding instead
AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AUTO,) * len(axes))


def make_debug_mesh(n_data: int | None = None, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    n_data = n_data or (n // n_model)
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AUTO,) * 2)
