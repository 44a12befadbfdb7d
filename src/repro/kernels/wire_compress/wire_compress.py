"""Fused wire-compressor kernel (QSGD quantize+pack) per plane.

The unfused QSGD wire path is a multi-launch XLA chain per plane —
abs/scale/floor/stochastic-round/clamp/sign ~6 elementwise kernels, then
an offset-encode + k strided shift/or packing steps, then a SEPARATE f32
scale leaf on the wire. ``qsgd_pack_pallas`` fuses the whole
quantize → offset-encode → sub-byte-pack chain into ONE kernel over the
wire plane, emitting the u8 byte image directly; the caller appends the
4 norm bytes so scale and values share a single wire buffer (one
collective-permute per round instead of two).

Two stages deliberately stay OUTSIDE the kernel:

* the uniform draw — ``jax.random.uniform(key, plane.shape)`` at the
  CANONICAL plane-spec shape, so the PRNG-hygiene lint (analyzer
  contract rule 3) sees the draw and the bits are bit-identical to the
  unfused ``QSGDCompressor``;
* the l2 norm — one whole-plane reduction whose in-kernel grid
  accumulation would change the reduction ORDER vs XLA and break
  bit-equality. The kernel receives 1/norm pre-scaled (``inv``).

The byte image is the unfused row-major flat order: byte ``b`` holds
elements ``b*k .. b*k+k-1`` (``k = 8 // bits``), element ``j`` at bit
``j*bits``. Gathering every k-th lane is a lane shuffle the TPU vector
unit has no cheap form for. Plane rows ``R*k .. R*k+k-1`` fill exactly
byte row R, so the kernel reads them as k sublane-strided slices and
packs each with a matmul against a constant 0/2^(i*bits) matrix that
routes lane t to byte lane ``j*LANE/k + t//k``. Every operand is a small
integer or a power of two (sums <= 255), so the bf16 MXU product is
exact and the image stays bit-identical to the unfused packer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.plane import LANE
from repro.kernels import resolve_interpret

__all__ = ["qsgd_pack_pallas", "LANE", "pack_factor"]

# Output rows per qsgd grid step: 1 MiB f32 input blocks at k=4, and a
# multiple of the (32, 128) u8 tile.
QSGD_BLOCK_ROWS = 512


def pack_factor(bits: int) -> int:
    """u8 lanes per byte: 8/bits for sub-byte widths, else unpacked."""
    return 8 // bits if bits in (2, 4) else 1


def _pack_matrices(bits: int) -> jax.Array:
    """(k, LANE, LANE) bf16: lane t of plane row ``R*k + j`` -> output
    lane ``j*LANE/k + t//k`` of byte row R, at bit ``(t % k) * bits``."""
    k = pack_factor(bits)
    t = np.arange(LANE)
    m = np.zeros((k, LANE, LANE), np.float32)
    for j in range(k):
        m[j, t, j * (LANE // k) + t // k] = 2.0 ** ((t % k) * bits)
    return jnp.asarray(m, jnp.bfloat16)


def _qsgd_kernel(x_ref, u_ref, inv_ref, *refs, bits: int):
    s = float(2 ** (bits - 1) - 1)
    k = pack_factor(bits)
    rows = x_ref.shape[0] // k

    def offsets(j):
        # plane rows j, j+k, j+2k, ... of this block; the EXACT unfused
        # arithmetic (compressor.QSGDCompressor.compress): floor +
        # stochastic carry + clamp + sign, fused into one pass.
        sl = pl.ds(j, rows, stride=k) if k > 1 else slice(None)
        xf = x_ref[sl, :]
        ratio = jnp.abs(xf) * inv_ref[0, 0]
        level = jnp.floor(ratio)
        level = level + (u_ref[sl, :] < (ratio - level))
        q = (jnp.sign(xf) * jnp.minimum(level, s)).astype(jnp.int32)
        return q + int(s)         # offset-encode to [0, 2s] < 2^bits

    if k == 1:
        (out_ref,) = refs
        out_ref[...] = offsets(0).astype(jnp.uint8)
        return
    pack_ref, out_ref = refs
    byte = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(k):
        byte = byte + jnp.dot(offsets(j).astype(jnp.bfloat16), pack_ref[j],
                              preferred_element_type=jnp.float32)
    out_ref[...] = byte.astype(jnp.int32).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def qsgd_pack_pallas(xf: jax.Array, u: jax.Array, inv: jax.Array, *,
                     bits: int, interpret: bool | None = None) -> jax.Array:
    """(rows, LANE) f32 plane + uniforms + (1, 1) 1/norm -> packed u8.

    Output is the flat ``rows * LANE // pack_factor`` u8 byte image the
    unfused packer produces in row-major flat order (offset-encoded
    q + s for bits=8).
    """
    rows, lane = xf.shape
    assert lane == LANE, (xf.shape,)
    k = pack_factor(bits)
    pad = (-rows) % k
    if pad:
        # zero pad rows only add whole trailing bytes, cut off below
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        u = jnp.pad(u, ((0, pad), (0, 0)))
    out_rows = (rows + pad) // k
    block = min(out_rows, QSGD_BLOCK_ROWS)
    blk_in = pl.BlockSpec((block * k, LANE), lambda i: (i, 0))
    in_specs = [blk_in, blk_in, pl.BlockSpec((1, 1), lambda i: (0, 0))]
    operands = [xf, u, inv]
    if k > 1:
        in_specs.append(pl.BlockSpec((k, LANE, LANE), lambda i: (0, 0, 0)))
        operands.append(_pack_matrices(bits))
    out = pl.pallas_call(
        functools.partial(_qsgd_kernel, bits=bits),
        grid=(pl.cdiv(out_rows, block),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, LANE), jnp.uint8),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return out.reshape(-1)[:rows * LANE // k]

