"""Wire plane: the whole differential as one contiguous padded buffer.

The paper's communication claim is about wire VOLUME, but a pytree-shaped
transport pays a per-leaf latency tax the paper never models: compressing
and ppermuting each parameter leaf separately serializes
``num_leaves x R`` collective-permutes (plus one sort / PRNG draw per
leaf) per gossip step — hundreds of small collectives on a real
transformer. The standard fix (cf. cpSGD's fixed-budget wire encoding and
DDP gradient bucketing) is to flatten the whole tree into one contiguous
**wire plane** and run the compressor / draw / exchange ONCE per plane:

    ParamPlane.for_tree(tree)   ->  static layout spec (hashable)
    spec.pack(tree)             ->  tuple of (rows, LANE) f32 planes
    spec.unpack(planes)         ->  tree (original shapes/dtypes)

so a compiled distributed step issues exactly R collective-permutes per
exchange **independent of the model's leaf count**, and one fixed-k draw
over the whole plane replaces per-leaf ``num_kept`` ceils.

Buckets
-------
A plane is ``(rows, lane)`` with leaves concatenated flat (row-major) and
zero-padded up to a ``lane * row_multiple`` multiple. Flattening destroys
tensor-parallel layouts, so leaves may carry a BUCKET key (see
``use_buckets``): leaves whose key is ``None`` join the default flat
bucket (lane = ``LANE``); leaves with key ``(mesh_axis, cols)`` — i.e.
their trailing dim is model-sharded — group into one plane per distinct
(key, trailing-dim) whose lane IS that trailing dim, packed as stacked
rows ``(size // cols, cols)``. Dim 1 of such a plane keeps the leaf's
model-axis sharding (exactly the old ``fixedk_rows`` trick, hoisted to
the plane level), so the ppermute payload stays tensor-parallel — one
plane per distinct inner sharding, like DDP gradient buckets. The bucket
policy is owned by the train-step factory (``repro.train.steps``), which
installs the key tree around tracing via ``use_buckets``; everything else
sees buckets only as "multiple planes".

The layout spec is frozen/hashable and cached per (treedef, shapes,
dtypes, bucket keys, lane, row_multiple) — safe to close over in
jit/shard_map, and both executors of a method derive the SAME spec from
the same parameter template, so draw granularity cannot diverge.

The kernel wrapper (``repro.kernels.sdm_update.ops``) reuses this exact
machinery with ``lane=1024, row_multiple=block_rows`` — the former
private ``_flatten`` there is gone.

Padding note: pad coordinates are zero on entry and stay zero through
every exchange (compressors scale zeros to zeros; ppermute delivers
zeros), so they are never informative — but they DO ride the wire, which
is why the wire accounting in ``sdm_dsgd.transmitted_*_per_step`` charges
plane-padded shapes (that is what the HLO payload actually is).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Tuple

import jax
import jax.numpy as jnp

__all__ = ["LANE", "PlaneBucket", "ParamPlane", "use_buckets",
           "current_bucket_keys", "bucket_keys_from_axes"]

PyTree = Any

# Wire-plane lane width: one TPU vector lane. Small enough that the zero
# pad is negligible against real models (< LANE * row_multiple elements
# per bucket), wide enough that (rows, LANE) planes are layout-friendly.
LANE = 128


# --------------------------------------------------------------------------
# Bucket-key context: the train-step factory owns the sharding policy.
# --------------------------------------------------------------------------

_STATE = threading.local()


# A bucket-key LEAF is None (default flat bucket) or a tuple key like
# ('model', cols) — container nodes (dicts, lists, ...) keep recursing,
# so key trees stay congruent with arbitrarily nested parameter trees.
_is_key_leaf = lambda v: v is None or isinstance(v, tuple)


def _flatten_keys(keys_tree):
    return jax.tree.flatten(keys_tree, is_leaf=_is_key_leaf)


@contextlib.contextmanager
def use_buckets(keys_tree: "PyTree | None"):
    """Install a per-leaf bucket-key tree for ``ParamPlane.for_tree``.

    ``keys_tree`` is congruent with the parameter tree; each leaf is a
    TUPLE bucket key (e.g. ``('model', cols)``) or ``None`` (default
    flat bucket) — see ``_is_key_leaf``. Installed around TRACING (it is
    static metadata), typically by ``steps.make_distributed_train`` and
    the matching state-template builders so the executor and the
    templates agree on the layout.
    """
    prev = getattr(_STATE, "buckets", None)
    if keys_tree is None:
        _STATE.buckets = None
    else:
        leaves, treedef = _flatten_keys(keys_tree)
        _STATE.buckets = (treedef, tuple(leaves))
    try:
        yield
    finally:
        _STATE.buckets = prev


def current_bucket_keys(treedef) -> "Tuple | None":
    """The installed key tuple when it matches ``treedef``, else None."""
    ctx = getattr(_STATE, "buckets", None)
    if ctx is not None and ctx[0] == treedef:
        return ctx[1]
    return None


def bucket_keys_from_axes(axes_tree: PyTree, shapes_tree: PyTree,
                          mapping) -> PyTree:
    """Derive bucket keys from logical-axis tuples (the steps.py policy).

    A leaf whose LAST logical axis maps to a mesh axis (e.g. 'model')
    gets key ``(mesh_axis, trailing_dim)`` — its plane rows keep the TP
    sharding; every other leaf joins the default flat bucket (``None``).
    """
    is_axes = lambda v: isinstance(v, tuple) and all(
        isinstance(e, (str, type(None))) for e in v)

    def one(axes, shape):
        if not axes or not shape:
            return None
        mesh_axis = mapping.get(axes[-1]) if axes[-1] is not None else None
        if mesh_axis is None:
            return None
        if isinstance(mesh_axis, (tuple, list)):
            mesh_axis = tuple(mesh_axis)
        return (mesh_axis, int(shape[-1]))

    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=is_axes)


# --------------------------------------------------------------------------
# The layout spec.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlaneBucket:
    """One plane of the layout: leaves sharing a sharding bucket."""

    key: Any                       # None = default flat bucket
    lane: int                      # plane width (cols)
    leaves: Tuple[int, ...]        # member leaf indices (tree-flatten order)
    sizes: Tuple[int, ...]         # flat element count per member
    rows: int                      # padded row count

    @property
    def size(self) -> int:
        """Unpadded element count (sum of member sizes)."""
        return sum(self.sizes)

    @property
    def padded_size(self) -> int:
        return self.rows * self.lane

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.rows, self.lane)


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


_SPECS: dict = {}
_SPECS_LOCK = threading.Lock()


@dataclasses.dataclass(frozen=True)
class ParamPlane:
    """Static flatten/unflatten layout for a parameter pytree.

    Frozen + hashable (the treedef and all geometry are static), so specs
    can be closed over in jit/shard_map and memoized. ``pack`` casts to
    f32 — the wire dtype — and ``unpack`` restores each leaf's shape and
    dtype.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    lane: int
    row_multiple: int
    buckets: Tuple[PlaneBucket, ...]

    # -- construction ------------------------------------------------------
    @classmethod
    def for_tree(cls, tree: PyTree, *, lane: int = LANE,
                 row_multiple: int = 1,
                 buckets: "PyTree | str" = "auto") -> "ParamPlane":
        """The (cached) layout spec of ``tree``.

        ``tree`` may hold arrays or ShapeDtypeStructs (only shape/dtype
        are read). ``buckets='auto'`` consults the ``use_buckets``
        context (no context -> one flat bucket); pass an explicit key
        tree or ``None`` to override.
        """
        leaves, treedef = jax.tree.flatten(tree)
        shapes = tuple(tuple(int(d) for d in l.shape) for l in leaves)
        dtypes = tuple(jnp.dtype(l.dtype).name for l in leaves)
        if buckets == "auto":
            keys = current_bucket_keys(treedef) or (None,) * len(leaves)
        elif buckets is None:
            keys = (None,) * len(leaves)
        else:
            keys = tuple(_flatten_keys(buckets)[0])
            if len(keys) != len(leaves):
                raise ValueError(
                    f"bucket key tree has {len(keys)} leaves for "
                    f"{len(leaves)} parameter leaves")
        cache_key = (treedef, shapes, dtypes, keys, lane, row_multiple)
        with _SPECS_LOCK:
            spec = _SPECS.get(cache_key)
            if spec is None:
                spec = cls._build(treedef, shapes, dtypes, keys, lane,
                                  row_multiple)
                _SPECS[cache_key] = spec
        return spec

    @classmethod
    def for_stacked(cls, stack: PyTree, **kw) -> "ParamPlane":
        """Spec of a node-stacked tree: leaves lose their leading axis."""
        per_node = jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(tuple(v.shape[1:]), v.dtype),
            stack)
        return cls.for_tree(per_node, **kw)

    @classmethod
    def _build(cls, treedef, shapes, dtypes, keys, lane, row_multiple
               ) -> "ParamPlane":
        groups: dict = {}
        order = []
        for i, (shape, key) in enumerate(zip(shapes, keys)):
            size = 1
            for d in shape:
                size *= d
            if key is not None:
                cols = shape[-1] if shape else 1
                if cols < 1 or size % cols:
                    raise ValueError(
                        f"bucket {key!r}: leaf {i} shape {shape} has no "
                        "whole trailing-dim rows")
                gkey = ("k", key, cols)
            else:
                gkey = ("flat",)
            if gkey not in groups:
                groups[gkey] = []
                order.append(gkey)
            groups[gkey].append((i, size))
        buckets = []
        for gkey in order:
            members = groups[gkey]
            idxs = tuple(i for i, _ in members)
            sizes = tuple(s for _, s in members)
            total = sum(sizes)
            if gkey[0] == "flat":
                b_lane = lane
                rows = _ceil_to(total, lane * row_multiple) // lane
                rows = max(rows, row_multiple)
                bkey = None
            else:
                _, bkey, b_lane = gkey
                rows = max(_ceil_to(total // b_lane, row_multiple),
                           row_multiple)
            buckets.append(PlaneBucket(key=bkey, lane=b_lane, leaves=idxs,
                                       sizes=sizes, rows=rows))
        return cls(treedef=treedef, shapes=shapes, dtypes=dtypes, lane=lane,
                   row_multiple=row_multiple, buckets=tuple(buckets))

    # -- geometry ----------------------------------------------------------
    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def total_size(self) -> int:
        """Unpadded element count over the whole tree."""
        return sum(b.size for b in self.buckets)

    @property
    def padded_size(self) -> int:
        """Wire element count: what the planes actually carry."""
        return sum(b.padded_size for b in self.buckets)

    def plane_shapes(self) -> Tuple[Tuple[int, int], ...]:
        return tuple(b.shape for b in self.buckets)

    def shape_dtype(self, dtype=jnp.float32) -> Tuple[jax.ShapeDtypeStruct, ...]:
        """Plane templates — also the tree wire accounting runs over."""
        return tuple(jax.ShapeDtypeStruct(b.shape, dtype)
                     for b in self.buckets)

    def zeros(self) -> Tuple[jax.Array, ...]:
        return tuple(jnp.zeros(b.shape, jnp.float32) for b in self.buckets)

    # -- pack / unpack -----------------------------------------------------
    def _leaves_of(self, tree: PyTree) -> list:
        leaves, treedef = jax.tree.flatten(tree)
        if len(leaves) != len(self.shapes):
            raise ValueError(
                f"tree has {len(leaves)} leaves, spec expects "
                f"{len(self.shapes)}")
        return leaves

    def pack(self, tree: PyTree) -> Tuple[jax.Array, ...]:
        """Concatenate the tree into the plane tuple (f32, zero-padded)."""
        leaves = self._leaves_of(tree)
        out = []
        for b in self.buckets:
            if b.key is None:
                parts = [leaves[i].reshape(-1).astype(jnp.float32)
                         for i in b.leaves]
                flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                pad = b.padded_size - b.size
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                out.append(flat.reshape(b.rows, b.lane))
            else:
                parts = [leaves[i].reshape(-1, b.lane).astype(jnp.float32)
                         for i in b.leaves]
                mat = parts[0] if len(parts) == 1 else \
                    jnp.concatenate(parts, axis=0)
                pad = b.rows - b.size // b.lane
                if pad:
                    mat = jnp.pad(mat, ((0, pad), (0, 0)))
                out.append(mat)
        return tuple(out)

    def unpack(self, planes: Tuple[jax.Array, ...]) -> PyTree:
        """Slice the planes back into the original tree (shapes + dtypes)."""
        if len(planes) != len(self.buckets):
            raise ValueError(
                f"{len(planes)} planes for {len(self.buckets)} buckets")
        leaves: list = [None] * len(self.shapes)
        for b, plane in zip(self.buckets, planes):
            flat = plane.reshape(-1)[:b.size]
            off = 0
            for i, size in zip(b.leaves, b.sizes):
                leaves[i] = flat[off:off + size].reshape(
                    self.shapes[i]).astype(self.dtypes[i])
                off += size
        return jax.tree.unflatten(self.treedef, leaves)

    # Stacked (leading node axis) variants for the reference executors.
    def pack_stacked(self, stack: PyTree) -> Tuple[jax.Array, ...]:
        """Per-node pack of a node-stacked tree -> (n, rows, lane) planes."""
        return jax.vmap(self.pack)(stack)

    def unpack_stacked(self, planes: Tuple[jax.Array, ...]) -> PyTree:
        return jax.vmap(self.unpack)(planes)
