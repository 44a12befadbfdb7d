"""Paged flash decode: block-table-aware single-token attention.

The serving engine's paged KV cache stores keys/values in fixed-size
pages (``repro.serving.kv_cache``); a per-slot block table maps logical
block j of request row b to a physical page id. This kernel reads the
cache THROUGH the table — pages are never gathered into a contiguous
buffer — using the canonical TPU structure: the block table and the
per-row valid lengths ride scalar prefetch, so each k-block's DMA source
index is computed before the kernel body runs.

grid = (batch, kv_heads, n_blocks); the innermost block dimension
accumulates into VMEM scratch (m, l, acc) exactly like the prefill
kernel in ``flash_attn.py``. GQA is handled by processing all ``group``
query heads of one kv head per program. The page pool is head-major,
``(n_pages, kv_heads, page_size, head_dim)``, so one program's K/V block
is a whole ``(page_size, head_dim)`` tile of one head — the layout the
TPU's (8, 128) sublane/lane tiling accepts (the ops wrapper pads
head_dim to 128). Like every kernel here it runs compiled on a TPU and
interpreted elsewhere; ``paged_attention_ref`` is the dense oracle
(``use_kernel=False``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

__all__ = ["paged_attention", "paged_attention_ref",
           "paged_flash_decode_pallas"]

NEG_INF = -1e30


def _kernel(tbl_ref, seq_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, window, softcap, page_size):
    bi = pl.program_id(0)
    ji = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(ji == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                      # (group, dh)
    k = k_ref[0, 0]                      # (page_size, dh)
    v = v_ref[0, 0]
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)

    seq_len = seq_ref[bi]                # valid tokens incl. current
    k_pos = ji * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], page_size), 1)
    mask = k_pos < seq_len
    if window is not None:
        mask &= k_pos > (seq_len - 1) - window
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    # explicit re-mask: on a fully-masked block m_new == NEG_INF and
    # exp(scores - m_new) would resurrect every entry as exp(0) == 1
    p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ji == nb - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "interpret"))
def paged_flash_decode_pallas(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_table: jax.Array,
                              seq_lens: jax.Array, *,
                              window: int | None = None,
                              softcap: float | None = None,
                              interpret: bool | None = None) -> jax.Array:
    """q: (b, kvh, group, dh); pages: (n_pages, kvh, page, dh);
    block_table: (b, n_blocks) int32; seq_lens: (b,) int32 ->
    (b, kvh, group, dh).

    Rows with seq_len == 0 (empty slots) produce zeros: every k position
    masks out, l stays 0 and the finalize divides the zero accumulator
    by the epsilon floor.
    """
    b, kvh, group, dh = q.shape
    page = k_pages.shape[2]
    n_blocks = block_table.shape[1]
    scale = 1.0 / math.sqrt(dh)
    grid = (b, kvh, n_blocks)
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               softcap=softcap, page_size=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,     # block_table, seq_lens
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, dh),
                         lambda bi, hi, ji, tbl, seq: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, page, dh),
                         lambda bi, hi, ji, tbl, seq: (tbl[bi, ji], hi, 0, 0)),
            pl.BlockSpec((1, 1, page, dh),
                         lambda bi, hi, ji, tbl, seq: (tbl[bi, ji], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, dh),
                               lambda bi, hi, ji, tbl, seq: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),    # running max m
            pltpu.VMEM((group, 1), jnp.float32),    # running sum l
            pltpu.VMEM((group, dh), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_table, seq_lens, q, k_pages, v_pages)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_table: jax.Array, seq_lens: jax.Array, *,
                        window: int | None = None,
                        softcap: float | None = None) -> jax.Array:
    """Dense oracle: gather pages through the table, masked softmax.

    q: (b, h, dh) -> (b, h, dh); pages (n_pages, kvh, page, dh).
    Materializes the (b, n_blocks*page) contiguous view.
    """
    b, h, dh = q.shape
    _, kvh, page, _ = k_pages.shape
    group = h // kvh
    # (b, nb, kvh, page, dh) -> (b, nb*page, kvh, dh)
    k = k_pages[block_table].transpose(0, 1, 3, 2, 4)
    v = v_pages[block_table].transpose(0, 1, 3, 2, 4)
    nb = k.shape[1]
    k = k.reshape(b, nb * page, kvh, dh)
    v = v.reshape(b, nb * page, kvh, dh)
    qg = q.reshape(b, kvh, group, dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k) / math.sqrt(dh)
    scores = scores.astype(jnp.float32)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    pos = jnp.arange(nb * page)
    mask = pos[None, :] < seq_lens[:, None]
    if window is not None:
        mask &= pos[None, :] > (seq_lens[:, None] - 1) - window
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v)
    # empty rows (seq_len 0): fully-masked softmax degenerates to uniform;
    # zero them so both paths agree that a dead slot contributes nothing.
    out = jnp.where((seq_lens > 0)[:, None, None, None], out, 0.0)
    return out.reshape(b, h, dh)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array, seq_lens: jax.Array, *,
                    window: int | None = None, softcap: float | None = None,
                    use_kernel: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """GQA-aware public entry. q: (b, h, dh) single decode token per row;
    k/v_pages: (n_pages, kv_heads, page, dh); block_table (b, n_blocks);
    seq_lens (b,) valid tokens per row (incl. the current one).
    """
    b, h, dh = q.shape
    kvh = k_pages.shape[1]
    group = h // kvh
    if not use_kernel:
        return paged_attention_ref(q, k_pages, v_pages, block_table,
                                   seq_lens, window=window, softcap=softcap)
    qg = q.reshape(b, kvh, group, dh)
    pad_d = (-dh) % 128
    if pad_d:
        # zero-padding head_dim adds nothing to q.k; rescale so the
        # kernel's 1/sqrt(dh_padded) matches 1/sqrt(dh).
        scale_fix = ((dh + pad_d) / dh) ** 0.5
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, pad_d))) * scale_fix
        k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
    out = paged_flash_decode_pallas(qg, k_pages, v_pages,
                                    block_table.astype(jnp.int32),
                                    seq_lens.astype(jnp.int32),
                                    window=window, softcap=softcap,
                                    interpret=interpret)
    return out[..., :dh].reshape(b, h, dh)
