"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.

Axes are ``Auto``: the sharding rules (``repro.launch.sharding``) and the
model's hints are GSPMD constraints, which ``jax.make_mesh``'s default
``Explicit`` axes would turn into sharding-in-types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host has (CPU smoke runs: 1 device -> 1x1 mesh)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def fsdp_axes(mesh) -> tuple:
    """The axes parameters/batch shard over (FSDP): pod+data when present."""
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))


def tp_axis(mesh) -> str:
    return "model"


def axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
