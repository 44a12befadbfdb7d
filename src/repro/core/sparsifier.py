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
    "fixedk_mask",
    "score_keys",
    "topk_mask",
    "mask_indices",
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


# ``jax.random.uniform`` makes a float32 score from 32 random bits as
# (bits >> 9) / 2**23, exactly: the scores' order is that of the 23-bit
# integer keys bits >> 9.
SCORE_BITS = 23
# keys per block of the tie count in ``topk_mask``
TIE_BLOCK = 1024
# entries per row of the in-row ranks in ``mask_indices``
RANK_BLOCK = 128


@jax.named_scope("sdm_draw")
def score_keys(key: jax.Array, d: int) -> jax.Array:
    """(d,) int32 keys in [0, 2**SCORE_BITS) ranked as the scores of
    ``fixedk_indices(key, d, k)``: ``topk_mask`` ranks them without a
    sort.
    """
    bits = jax.random.bits(key, (d,), jnp.uint32)
    # one copy of the keys in memory: without the barrier XLA recomputes
    # their producer (the random bits) inside every pass that reads them
    return jax.lax.optimization_barrier(
        (bits >> (32 - SCORE_BITS)).astype(jnp.int32))


@jax.named_scope("sdm_draw")
def fixedk_mask(key: jax.Array, d: int, k) -> jax.Array:
    """(d,) bool mask of exactly the set ``fixedk_indices(key, d, k)`` holds.

    The same scores ranked by counting instead of sorting (see
    ``topk_mask``); ``k`` may be a traced int32 scalar.
    """
    return topk_mask(score_keys(key, d), k, SCORE_BITS)


@jax.named_scope("sdm_draw")
def topk_mask(m: jax.Array, k, bits: int) -> jax.Array:
    """Mask of the set ``lax.top_k(m, k)`` selects, without a sort.

    ``m`` holds (d,) int32 keys in [0, 2**bits). ``top_k`` keeps every key
    above the k-th largest key t, and of the keys equal to t the r
    lowest-index ones, r = k - count(m > t) (ties go to the lower index).
    t is found by a 16-way search, each step one pass over ``m`` that
    counts the keys at or above 15 thresholds (ceil(bits / 4) passes);
    the index cut by one pass that counts the ties in each block of
    ``TIE_BLOCK`` keys, then a running count within the one block that
    holds the r-th tie. No sort, no full-length cumsum.
    """
    d = m.shape[0]
    k = jnp.asarray(k, jnp.int32)

    def count(pred):
        return jnp.sum(pred, dtype=jnp.int32)

    # t: count(m >= lo) >= k > count(m >= lo + width) = n_hi
    lo, n_hi, width_bits = jnp.int32(0), jnp.int32(0), bits
    while width_bits:
        ways = 1 << min(4, width_bits)
        width_bits -= min(4, width_bits)
        step = 1 << width_bits
        at_or_above = jnp.stack([count(m >= lo + j * step)
                                 for j in range(1, ways)])
        j = count(at_or_above >= k)       # thresholds that keep >= k keys
        n_hi = jnp.where(j < ways - 1,
                         at_or_above[jnp.minimum(j, ways - 2)], n_hi)
        lo = lo + j * step
    t, r = lo, k - n_hi          # r >= 1: the ties at t that top_k keeps
    # c: the index just past the r-th tie
    blocks = -(-d // TIE_BLOCK)
    tie = jnp.pad(m == t, (0, blocks * TIE_BLOCK - d)).reshape(blocks, -1)
    ties_through = jnp.cumsum(jnp.sum(tie, axis=1, dtype=jnp.int32))
    b = count(ties_through < r)          # the block that holds the r-th tie
    before = jnp.where(b > 0, ties_through[jnp.maximum(b - 1, 0)], 0)
    row = jax.lax.dynamic_index_in_dim(tie, b, keepdims=False)
    c = b * TIE_BLOCK + count(
        before + jnp.cumsum(row, dtype=jnp.int32) < r) + 1
    iota = jax.lax.iota(jnp.int32, d)
    return (m > t) | ((m == t) & (iota < c))


@jax.named_scope("sdm_draw")
def mask_indices(mask: jax.Array, k: int) -> jax.Array:
    """(k,) int32: the ascending indices of the first k <= d true entries
    of the (d,) bool ``mask``, as ``jnp.nonzero(mask, size=k)`` lists them;
    where the mask holds fewer, the rest read d.

    No sort, no scatter and no full-length cumsum. Each kept entry moves
    left by the s entries not kept before it: s from its count within a
    row of ``RANK_BLOCK`` entries (one 0/1 matmul with a triangular
    matrix, exact: counts of at most RANK_BLOCK, accumulated in f32) and
    the running count of the rows before. The move is made one bit of s
    at a time, lowest first, each bit one dense pass that shifts the
    entries with that bit set by its weight: the entries' order is kept,
    so no two ever land on one place. The entries carry s itself, and the
    one that ends at place q is index q + s. Exact for any d < 2**31;
    ``mask`` may come from ``topk_mask`` with a traced k.
    """
    d = mask.shape[0]
    rows = -(-d // RANK_BLOCK)
    m = jnp.pad(mask, (0, rows * RANK_BLOCK - d)).reshape(rows, RANK_BLOCK)
    j = jax.lax.iota(jnp.int32, RANK_BLOCK)
    upper = (j[:, None] <= j[None, :]).astype(jnp.bfloat16)
    through = jnp.dot(m.astype(jnp.bfloat16), upper,
                      preferred_element_type=jnp.float32).astype(jnp.int32)
    count = through[:, -1]
    rank = (jnp.cumsum(count) - count)[:, None] + through - 1   # kept before
    index = jax.lax.iota(jnp.int32, rows)[:, None] * RANK_BLOCK + j
    s = jnp.where(m, index - rank, -1).reshape(-1)[:d]   # -1: no entry
    for b in range(max(d - 1, 1).bit_length()):
        moves = (s >= 0) & ((s >> b) & 1 == 1)
        arriving = jnp.pad(jnp.where(moves, s, -1)[1 << b:], (0, 1 << b),
                           constant_values=-1)
        s = jnp.where(moves | (s < 0), arriving, s)
    s = s[:k]
    return jnp.where(s >= 0, jax.lax.iota(jnp.int32, k) + s, d)


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
