"""Production mesh construction.

Never touches jax device state at import time — mesh creation is a function.
Single pod: (data=16, model=16) = 256 chips (v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis carries
only data parallelism (gradient all-reduce crosses DCN once per step).
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """jax.make_mesh with every axis of type Auto."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


_make = make_mesh_compat


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return _make((n // model, model), ("data", "model"))
