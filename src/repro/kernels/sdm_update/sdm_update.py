"""Fused SDM-DSGD iteration update kernel (Algorithm 1's elementwise core).

One HBM pass over the flat parameter vector fuses what would otherwise be
~9 separate elementwise kernels (clip, noise synth, axpy chain, mask,
scale, three state updates):

    s      = s_prev + nb_sum                        (gossip accumulation)
    g_hat  = clip(g, +-clip_c) + sigma * N(0,1)     (Gaussian masking)
    y      = (1-theta)*x + theta*(w_self*x + s - gamma*g_hat)
    d_new  = y - x
    sd     = bernoulli_mask(p) * d_new / p          (sparsifier S(.))
    x_new  = x + sd

The Gaussian is synthesized IN-KERNEL from two uniform u32 bit streams
via Box-Muller, and the Bernoulli mask from a third — so the random bits
(cheap int32) are the only extra traffic and the f32 noise tensors never
touch HBM. On real TPUs the bits themselves can come from the hardware
PRNG (``use_device_prng=True`` in ops.py); that path cannot execute in
CPU interpret mode (no ``prng_seed`` lowering — verified), so validation
feeds explicit bits.

Tiling: the flat vector is padded and reshaped to (rows, 1024) f32 —
1024 = 8 VREG lanes x 128 sublanes; each grid step processes a
(block_rows, 1024) VMEM tile (block_rows=256 -> 1 MiB per operand tile,
7 inputs + 3 outputs ~= 10 MiB of VMEM, inside the ~16 MiB budget).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

__all__ = ["sdm_update_pallas", "LANE", "DEFAULT_BLOCK_ROWS"]

LANE = 1024
DEFAULT_BLOCK_ROWS = 256

_TWO_PI = 2.0 * math.pi
_INV24 = 1.0 / (1 << 24)


def _uniform01(bits: jax.Array) -> jax.Array:
    """Top-24-bit uniform in (0, 1]; never 0 so log() is safe."""
    u = (bits >> 8).astype(jnp.float32) * _INV24
    return jnp.maximum(u, _INV24)


def _kernel(x_ref, s_ref, nb_ref, g_ref, mbits_ref, n1_ref, n2_ref,
            xo_ref, so_ref, sd_ref, *, p, theta, gamma, sigma, clip_c,
            self_w):
    x = x_ref[...]
    s = s_ref[...] + nb_ref[...]
    g = g_ref[...]
    if clip_c is not None:
        g = jnp.clip(g, -clip_c, clip_c)
    if sigma > 0.0:
        u1 = _uniform01(n1_ref[...])
        u2 = _uniform01(n2_ref[...])
        gauss = jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(_TWO_PI * u2)
        g = g + sigma * gauss
    y = (1.0 - theta) * x + theta * (self_w * x + s - gamma * g)
    d = y - x
    keep = _uniform01(mbits_ref[...]) < p
    sd = jnp.where(keep, d * (1.0 / p), 0.0)
    xo_ref[...] = x + sd
    so_ref[...] = s
    sd_ref[...] = sd


@functools.partial(jax.jit, static_argnames=(
    "p", "theta", "gamma", "sigma", "clip_c", "self_w", "block_rows",
    "interpret"))
def sdm_update_pallas(x: jax.Array, s: jax.Array, nb_sum: jax.Array,
                      g: jax.Array, mask_bits: jax.Array, n1_bits: jax.Array,
                      n2_bits: jax.Array, *, p: float, theta: float,
                      gamma: float, sigma: float, clip_c: float | None,
                      self_w: float,
                      block_rows: int = DEFAULT_BLOCK_ROWS,
                      interpret: bool | None = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All operands (rows, LANE) f32 / u32, rows % block_rows == 0.

    Returns (x_new, s_new, sd).
    """
    rows, lane = x.shape
    assert lane == LANE and rows % block_rows == 0, (x.shape, block_rows)
    grid = (rows // block_rows,)
    blk = lambda: pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    kernel = functools.partial(_kernel, p=p, theta=theta, gamma=gamma,
                               sigma=sigma, clip_c=clip_c, self_w=self_w)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)] * 3
    return tuple(pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blk() for _ in range(7)],
        out_specs=[blk() for _ in range(3)],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(x, s, nb_sum, g, mask_bits, n1_bits, n2_bits))
