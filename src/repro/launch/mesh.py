"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: 16×16 = 256 chips (v5e pod slice); multi-pod
adds a leading ``pod`` axis (2×16×16 = 512 chips) — the pod axis carries
data-parallel gradient reduction only (weights are replicated across pods,
FSDP-sharded within a pod; DESIGN.md §6).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    # Auto axes: the sharding rules are GSPMD constraints
    # (distributed/sharding.py, distributed/ctx.py), not sharding-in-types,
    # which ``jax.make_mesh`` would otherwise default to
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model: int = 1, devices=None):
    """Tiny mesh over ``devices`` (default: every local device)."""
    devices = jax.devices() if devices is None else list(devices)
    data = max(len(devices) // model, 1)
    return _mesh((data, model), ("data", "model"), devices)
