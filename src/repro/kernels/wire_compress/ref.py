"""Pure-jnp oracle for the fused wire-compressor kernel.

Bit-identical to both the pallas kernel and the unfused
``compressor.QSGDCompressor`` chain — the property tests pin all three
to the same byte image.
"""
from __future__ import annotations

import jax.numpy as jnp

from .wire_compress import pack_factor


def qsgd_quantize_pack_ref(xf, u, inv, *, bits: int):
    """Quantize + offset-encode + sub-byte-pack, any input shape.

    ``inv`` must be the pre-computed ``s / max(norm, 1e-30)`` scalar so
    the multiply matches the unfused arithmetic exactly. Returns the
    FLAT u8 byte vector (row-major pack order, zero-padded to a whole
    byte), excluding the norm tail.
    """
    s = float(2 ** (bits - 1) - 1)
    ratio = jnp.abs(xf) * inv
    level = jnp.floor(ratio)
    level = level + (u < (ratio - level))
    q = (jnp.sign(xf) * jnp.minimum(level, s)).astype(jnp.int32)
    off = (q + int(s)).reshape(-1)
    k = pack_factor(bits)
    if k == 1:
        return off.astype(jnp.uint8)
    pad = (-off.shape[0]) % k
    if pad:
        off = jnp.pad(off, (0, pad))
    groups = off.reshape(-1, k)
    byte = jnp.zeros((groups.shape[0],), jnp.int32)
    for j in range(k):
        byte = byte | (groups[:, j] << (j * bits))
    return byte.astype(jnp.uint8)


def qsgd_decode_ref(buf, shape, *, bits: int):
    """Decode the fused single-buffer payload back to f32 (oracle for
    ``FusedQSGDCompressor.decompress``)."""
    import jax

    s = float(2 ** (bits - 1) - 1)
    import math as _math
    d = int(_math.prod(shape))
    k = pack_factor(bits)
    norm = jax.lax.bitcast_convert_type(buf[-4:], jnp.float32)
    data = buf[:-4].astype(jnp.int32)
    if k == 1:
        flat = data[:d] - int(s)
    else:
        mask = (1 << bits) - 1
        parts = [(data >> (j * bits)) & mask for j in range(k)]
        flat = jnp.stack(parts, axis=1).reshape(-1)[:d] - int(s)
    return (norm / s) * flat.reshape(shape).astype(jnp.float32)

