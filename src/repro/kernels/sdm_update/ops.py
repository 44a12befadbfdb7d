"""Public wrapper: flat-pytree SDM-DSGD fused update.

Flattens a parameter pytree into the kernel's (rows, 1024) layout via
the SHARED wire-plane machinery (``repro.core.plane.ParamPlane`` with
``lane=1024, row_multiple=block_rows`` — the former private ``_flatten``
here is gone, and the layout spec is computed ONCE instead of once per
operand), generates the three uniform bit streams with jax.random (or,
on real TPU hardware, leaves generation to the in-kernel PRNG), runs the
fused kernel, and unflattens. Drop-in replacement for the unfused
distributed_commit+advance pair's elementwise work.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core.plane import ParamPlane
from repro.kernels.sdm_update.sdm_update import (LANE, DEFAULT_BLOCK_ROWS,
                                                 sdm_update_pallas)
from repro.kernels.sdm_update import ref as ref_mod

PyTree = Any


def sdm_update(x_tree: PyTree, s_tree: PyTree, nb_tree: PyTree,
               g_tree: PyTree, key: jax.Array, *, p: float, theta: float,
               gamma: float, sigma: float, clip_c: float | None,
               self_w: float, block_rows: int = DEFAULT_BLOCK_ROWS,
               use_kernel: bool = True, interpret: bool | None = None
               ) -> Tuple[PyTree, PyTree, PyTree]:
    """Returns (x_new, s_new, sd) trees. ``key`` drives mask+noise bits."""
    spec = ParamPlane.for_tree(x_tree, lane=LANE, row_multiple=block_rows,
                               buckets=None)
    assert spec.n_buckets == 1, "kernel plane is bucket-free by construction"
    x = spec.pack(x_tree)[0]
    s = spec.pack(s_tree)[0]
    nb = spec.pack(nb_tree)[0]
    g = spec.pack(g_tree)[0]
    kb, k1, k2 = jax.random.split(key, 3)
    # Draw bits at the canonical LANE-padded size, NOT x.shape: threefry
    # output depends on the total draw size, so tying the draw to the
    # block_rows tile padding would make the mask (and the whole
    # trajectory) change with the kernel's tiling parameter.
    n_rows = -(-spec.total_size // LANE)

    def bits(k: jax.Array) -> jax.Array:
        b = jax.random.bits(k, (n_rows, LANE), jnp.uint32)
        return jnp.pad(b, ((0, x.shape[0] - n_rows), (0, 0)))
    fn = sdm_update_pallas if use_kernel else _ref_adapter
    x2, s2, sd = fn(x, s, nb, g, bits(kb), bits(k1), bits(k2), p=p,
                    theta=theta, gamma=gamma, sigma=sigma, clip_c=clip_c,
                    self_w=self_w,
                    **({"block_rows": block_rows, "interpret": interpret}
                       if use_kernel else {}))
    return (spec.unpack((x2,)), spec.unpack((s2,)), spec.unpack((sd,)))


def _ref_adapter(x, s, nb, g, mb, n1, n2, **kw):
    return ref_mod.sdm_update_ref(x, s, nb, g, mb, n1, n2, **kw)
