"""Production mesh definitions.

Axis conventions (shared with ``repro.dist.sharding``):

  single-pod : ("data", "model")          = (16, 16)   -> 256 chips
  multi-pod  : ("pod", "data", "model")   = (2, 16, 16) -> 512 chips

``model`` carries tensor parallelism; ``data`` (joined by ``pod`` in
multi-pod mode) carries batch data-parallelism and FSDP param sharding.
The Legio runtime shrinks along the data/pod axes only — a failed host takes
its ICI slice with it, so the model axis is never fractured by a fault
(see core/mesh_manager.py).

Everything here is a function, never a module-level constant: importing this
module must not touch jax device state (the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_named_mesh(shape, axes)


def make_named_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """Arbitrary mesh with Auto axis types (tests / small dry-runs)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size


def describe(mesh: Mesh) -> str:
    dims = "x".join(str(s) for s in mesh.devices.shape)
    return f"{dims} ({','.join(mesh.axis_names)}) = {mesh.devices.size} chips"
