"""The Bernoulli sparsifier S(.) of Definition 2 plus the packed fixed-k variant.

Definition 2 (paper §3): for x in R^d and p in (0, 1],
    [S(x)]_i = x_i / p   with probability p
    [S(x)]_i = 0         with probability 1-p
so that E[S(x)] = x (unbiased) and Var = (1/p - 1) ||x||^2 (Lemma 1, §3).

Two realizations:

* ``bernoulli_sparsify`` — the paper-faithful i.i.d. per-coordinate mask.
  The output is a dense tensor with ~ (1-p) d zeros; this is what the
  paper's theory analyses and what the CPU experiments use.

* ``fixedk_*`` — the TPU "packed" adaptation (DESIGN.md §2): exactly
  k = ceil(p*d) coordinates are chosen uniformly at random from a seed
  both endpoints can regenerate, so only k values ever cross the wire
  (a static-shape `collective-permute` operand). Selection probability
  per coordinate is k/d = p and kept values are scaled by d/k = 1/p,
  so unbiasedness is preserved; coordinates are no longer independent
  (slightly *lower* variance than i.i.d. Bernoulli by negative
  correlation — strictly favourable for the Lemma-1 terms).

Everything here operates on flat vectors; pytree handling lives in
``sdm_dsgd.py`` (a single flat offset-map keeps masks consistent across
leaves).
"""
from __future__ import annotations

import decimal
import functools
import math
from fractions import Fraction
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "bernoulli_mask",
    "bernoulli_sparsify",
    "fixedk_indices",
    "fixedk_pack",
    "fixedk_unpack",
    "fixedk_sparsify",
    "sparsifier_variance",
    "num_kept",
    "block_view",
    "block_sparsify",
]


def bernoulli_mask(key: jax.Array, shape: Tuple[int, ...], p: float) -> jax.Array:
    """Boolean keep-mask with i.i.d. keep-probability p."""
    return jax.random.bernoulli(key, p=p, shape=shape)


def bernoulli_sparsify(key: jax.Array, x: jax.Array, p) -> jax.Array:
    """Paper-faithful S(x): keep each coordinate w.p. p, scale kept by 1/p.

    ``p`` is a python float (static) or a traced scalar — the latter
    carries a per-node transmit probability (heterogeneous sparsity
    budgets): the keep-mask is ``uniform < p`` either way, so a node's
    draws for equal p agree bit-for-bit between the two forms.
    """
    if isinstance(p, (int, float)):
        if not 0.0 < p <= 1.0:
            raise ValueError(f"p must be in (0, 1], got {p}")
        if p == 1.0:
            return x
    mask = bernoulli_mask(key, x.shape, p)
    return jnp.where(mask, x / p, jnp.zeros_like(x))


def sparsifier_variance(x: jax.Array, p: float) -> jax.Array:
    """Lemma 1 (§3): Var(S(x)) = (1/p - 1) ||x||_2^2 (total, summed over coords)."""
    return (1.0 / p - 1.0) * jnp.sum(jnp.square(x))


# --------------------------------------------------------------------------
# Fixed-count ("packed") sparsification: the communication-real variant.
# --------------------------------------------------------------------------

@jax.named_scope("sdm_draw")
def fixedk_indices(key: jax.Array, d: int, k: int) -> jax.Array:
    """k distinct uniform indices into [0, d), regenerable from ``key``.

    Uses argtop-k of i.i.d. uniforms — equivalent to sampling without
    replacement, O(d log d) once per round (amortized: tiny vs model math).
    """
    scores = jax.random.uniform(key, (d,))
    _, idx = jax.lax.top_k(scores, k)
    return idx


def fixedk_pack(x_flat: jax.Array, idx: jax.Array, d: int) -> jax.Array:
    """Gather the selected coordinates and pre-scale by d/k (= 1/p_effective).

    The exact inclusion probability of each coordinate is k/d, so the
    unbiased scale is d/k (equals 1/p when p*d is integral). Shape (k,).
    """
    k = idx.shape[0]
    return jnp.take(x_flat, idx, axis=0) * (d / k)


def fixedk_unpack(values: jax.Array, idx: jax.Array, d: int) -> jax.Array:
    """Scatter packed values back to a dense (d,) vector of S(x)."""
    out = jnp.zeros((d,), dtype=values.dtype)
    return out.at[idx].set(values)


def fixedk_sparsify(key: jax.Array, x_flat: jax.Array, p: float) -> jax.Array:
    """Dense-output fixed-k sparsifier (for testing against the packed path)."""
    d = x_flat.shape[0]
    k = num_kept(d, p)
    idx = fixedk_indices(key, d, k)
    return fixedk_unpack(fixedk_pack(x_flat, idx, d), idx, d)


@functools.lru_cache(maxsize=None)
def num_kept(d: int, p: float) -> int:
    """k = ceil(p * d), at least 1, at most d.

    The ceiling is computed in EXACT arithmetic: naive ceil(d * p)
    overshoots whenever the float product lands epsilon above the true
    value (e.g. 100 * 0.07 == 7.000000000000001 -> 8), breaking the
    "exactly k = ceil(p*d)" contract and every byte-accounting consumer
    — and decimal-rounding workarounds fail again once d*p > ~2e7 where
    the float ulp exceeds the rounding threshold. ``repr(p)`` is the
    shortest decimal that round-trips to p, i.e. the number the caller
    actually wrote; the Fraction of that is exact at any scale. Cached,
    so the exact-arithmetic cost is paid once per (d, p).
    """
    p_exact = Fraction(decimal.Decimal(repr(p)))
    return min(d, max(1, math.ceil(p_exact * d)))


# --------------------------------------------------------------------------
# Block-granular fixed-k: transmit whole contiguous blocks of coordinates.
# --------------------------------------------------------------------------
#
# For billion-element leaves, element-granular top_k is both illegal
# (int32 index overflow beyond 2^31 elements) and wasteful (a giant sort
# per round). Real systems sparsify at bucket granularity; here blocks of
# ``block`` consecutive coordinates are kept/dropped together:
# inclusion probability per coordinate is k_blocks/n_blocks ~= p and the
# kept blocks are scaled by n_blocks/k_blocks, so Lemma 1's unbiasedness
# is preserved (coordinates within a block are fully correlated, across
# blocks negatively correlated). ``block=1`` reduces exactly to the
# element-granular scheme.

def block_view(x_flat: jax.Array, block: int) -> jax.Array:
    """Pad to a block multiple and reshape to (n_blocks, block)."""
    d = x_flat.shape[0]
    pad = (-d) % block
    if pad:
        x_flat = jnp.pad(x_flat, (0, pad))
    return x_flat.reshape(-1, block)


def block_sparsify(key: jax.Array, x_flat: jax.Array, p: float,
                   block: int) -> jax.Array:
    """Dense-output block-granular fixed-k sparsifier."""
    d = x_flat.shape[0]
    xb = block_view(x_flat, block)
    nb = xb.shape[0]
    kb = num_kept(nb, p)
    idx = fixedk_indices(key, nb, kb)
    vals = jnp.take(xb, idx, axis=0) * (nb / kb)
    out = jnp.zeros_like(xb).at[idx].set(vals)
    return out.reshape(-1)[:d]
