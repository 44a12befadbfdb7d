"""Logical-axis sharding: MaxText-style named-axis rules, mesh-agnostic models.

Model code annotates activations with ``logical(x, 'batch', 'seq', 'embed')``
and parameters carry logical axis tuples. The launcher installs a
``MeshRules`` mapping logical names -> mesh axes; with no rules installed
(CPU tests) every annotation is a no-op.

Divisibility fallback: if a dimension is not divisible by the mapped mesh
axis size (e.g. 4 KV heads over a 16-wide model axis), that dimension is
silently replicated — the standard behaviour production frameworks use
for small GQA heads.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["MeshRules", "use_rules", "current_rules", "logical",
           "logical_sharding", "tree_shardings"]

_STATE = threading.local()


class MeshRules:
    """mesh + {logical axis name -> mesh axis (str | tuple | None)}."""

    def __init__(self, mesh: Mesh, mapping: Mapping[str, Any]):
        self.mesh = mesh
        self.mapping = dict(mapping)

    def _axis_size(self, axis) -> int:
        if axis is None:
            return 1
        if isinstance(axis, (tuple, list)):
            out = 1
            for a in axis:
                out *= self.mesh.shape[a]
            return out
        return self.mesh.shape[axis]

    def spec(self, axes: Sequence[Optional[str]],
             shape: Optional[Sequence[int]] = None) -> P:
        """PartitionSpec from logical names, with divisibility fallback."""
        used: set = set()
        parts = []
        for i, name in enumerate(axes):
            mesh_axis = self.mapping.get(name) if name is not None else None
            if mesh_axis is None:
                parts.append(None)
                continue
            flat = tuple(mesh_axis) if isinstance(mesh_axis, (tuple, list)) \
                else (mesh_axis,)
            if any(a not in self.mesh.shape for a in flat):
                parts.append(None)  # mesh without this axis (debug meshes)
                continue
            if any(a in used for a in flat):
                parts.append(None)  # each mesh axis at most once per spec
                continue
            if shape is not None and shape[i] % self._axis_size(mesh_axis) != 0:
                parts.append(None)  # replicate non-divisible dims
                continue
            used.update(flat)
            parts.append(tuple(flat) if len(flat) > 1 else flat[0])
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding(self, axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(axes, shape))


def current_rules() -> Optional[MeshRules]:
    return getattr(_STATE, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[MeshRules]):
    prev = current_rules()
    _STATE.rules = rules
    try:
        yield
    finally:
        _STATE.rules = prev


def logical(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain activation sharding by logical names (no-op without rules).

    Inside a shard_map manual region the constraint must bind to the
    ambient *abstract* mesh (whose manual axes are typed Manual), not the
    concrete mesh the rules were built with — we rebuild the NamedSharding
    against the current abstract mesh when one is active.
    """
    rules = current_rules()
    if rules is None:
        return x
    assert len(axes) == x.ndim, (axes, x.shape)
    spec = rules.spec(axes, x.shape)
    abstract = jax.sharding.get_abstract_mesh()
    if abstract.shape_tuple:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(abstract, spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


def logical_sharding(axes: Sequence[Optional[str]],
                     shape: Sequence[int]) -> Optional[NamedSharding]:
    rules = current_rules()
    if rules is None:
        return None
    return rules.sharding(axes, shape)


def tree_shardings(rules: MeshRules, axes_tree: Any, shape_tree: Any) -> Any:
    """NamedSharding tree from parallel (axes, shapes) trees."""
    return jax.tree.map(
        lambda axes, shape: rules.sharding(axes, shape),
        axes_tree, shape_tree,
        is_leaf=lambda v: isinstance(v, tuple) and all(
            isinstance(e, (str, type(None))) for e in v))
