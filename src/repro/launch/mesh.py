"""Mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.

Every mesh in the repo is built by :func:`make_mesh`, which marks all
axes ``AxisType.Auto``: the serving steps place their intermediates with
``with_sharding_constraint`` and leave propagation to GSPMD, which jax
only accepts on Auto axes (``jax.make_mesh`` defaults to Explicit).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

XLA_LHS_FLAGS = (
    # collective/compute overlap knobs for real-TPU runs (documented here,
    # consumed by launch scripts; harmless on CPU):
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_enable_async_collective_fusion=true "
)


def make_mesh(shape: Sequence[int],
              axes: Sequence[str] = ("data", "model"), *,
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 chips per pod; 2 pods multi-pod (assignment contract)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1) -> jax.sharding.Mesh:
    """Whatever this host offers (tests / examples / one chip host)."""
    n = len(jax.devices())
    if model < 1 or n % model != 0:
        raise ValueError(
            f"model={model} must divide the host device count ({n})")
    data = n // model
    return make_mesh((data, model))
