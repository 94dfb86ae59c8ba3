"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init;
tests and benches must keep seeing 1 device).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is the
DCN-connected dimension (data parallelism across pods).
"""
from __future__ import annotations

import jax

from repro.dist import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh: jax.sharding.Mesh):
    """Mesh axes used for batch data parallelism."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def seq_axes_long(mesh: jax.sharding.Mesh):
    """Axes used to shard the KV cache sequence dim for long_500k (batch=1)."""
    return (
        ("pod", "data", "model") if "pod" in mesh.axis_names else ("data", "model")
    )
