"""Blockwise online-softmax (flash) attention for TPU prefill.

Canonical Pallas structure: grid = (batch*heads, q_blocks, k_blocks) with
the innermost k dimension accumulating into VMEM scratch (m, l, acc) that
persists across the sequential innermost grid steps on TPU. Supports
causal masking, sliding windows (gemma2 local layers), and gemma2-style
score soft-capping. MXU alignment: block_q x head_dim and
block_k x head_dim tiles, head_dim padded to 128 multiples by ops.py.

On-TPU refinement (not needed for interpret-mode validation): fully
masked k-blocks under causal/window masking could be skipped by shrinking
the k grid per q index; XLA-level cost is identical for the roofline.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, softcap, block_q, block_k, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                          # (block_q, dh)
    k = k_ref[0]                          # (block_k, dh)
    v = v_ref[0]
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # renormalize previous accumulator, accumulate this block
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           softcap: float | None = None, block_q: int = 128,
                           block_k: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q: (bh, sq, dh); k/v: (bh, skv, dh) — heads pre-flattened into bh.

    sq % block_q == 0; skv is padded to block_k internally (masked).
    """
    bh, sq, dh = q.shape
    skv = k.shape[1]
    assert sq % block_q == 0, (sq, block_q)
    pad_k = (-skv) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    skv_pad = skv + pad_k
    grid = (bh, sq // block_q, skv_pad // block_k)
    scale = 1.0 / math.sqrt(dh)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, kv_len=skv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, dh), jnp.float32),  # output accumulator
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
