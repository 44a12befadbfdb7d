"""Public jit-friendly entry points for the fused wire-compressor.

``qsgd_pack`` is the quantize+pack stage of the fused QSGD wire format
("qsgdf"): callers draw the stochastic-rounding uniforms at the
CANONICAL plane shape and pass the raw l2 norm; this wrapper derives the
``s / max(norm, eps)`` scalar exactly as the unfused compressor does and
routes every wire plane through the pallas kernel (the pure-jnp oracle
serves non-plane shapes and ``use_kernel=False``). Output is the flat
u8 byte image, bit-identical across kernel, oracle and the unfused
``QSGDCompressor`` packer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .wire_compress import LANE, qsgd_pack_pallas

__all__ = ["qsgd_pack"]


@functools.partial(jax.jit,
                   static_argnames=("bits", "use_kernel", "interpret"))
def qsgd_pack(xf: jax.Array, u: jax.Array, norm: jax.Array, *, bits: int,
              use_kernel: bool = True,
              interpret: bool | None = None) -> jax.Array:
    """f32 tensor + uniforms + scalar norm -> flat packed u8 bytes."""
    xf = xf.astype(jnp.float32)
    s = float(2 ** (bits - 1) - 1)
    inv = s / jnp.maximum(norm, 1e-30)   # EXACT unfused scale arithmetic
    # any plane whose lane dim is a LANE multiple (the flat bucket and the
    # tensor-parallel buckets alike) is a free row-major (-1, LANE) view
    plane_like = xf.ndim == 2 and xf.shape[1] % LANE == 0
    if use_kernel and plane_like and bits in (2, 4, 8):
        return qsgd_pack_pallas(xf.reshape(-1, LANE), u.reshape(-1, LANE),
                                inv.reshape(1, 1), bits=bits,
                                interpret=interpret)
    return ref.qsgd_quantize_pack_ref(xf, u, inv, bits=bits)

