"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state — required for the dry-run's
XLA_FLAGS ordering and for tests that run on 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh_by_name", "node_axis_names"]


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned outside
    the node-manual shard_map regions)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh_by_name(name: str) -> jax.sharding.Mesh:
    """"local": every local device on the ``data`` axis (one SDM-DSGD
    node per device)."""
    if name == "local":
        return _auto_mesh((jax.local_device_count(),), ("data",))
    if name in ("single_pod", "16x16"):
        return make_production_mesh(multi_pod=False)
    if name in ("multi_pod", "2x16x16"):
        return make_production_mesh(multi_pod=True)
    # small debug meshes, e.g. "2x4"
    dims = tuple(int(d) for d in name.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return _auto_mesh(dims, axes)


def node_axis_names(mesh: jax.sharding.Mesh):
    return tuple(a for a in mesh.axis_names if a != "model")
