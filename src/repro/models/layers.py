"""Parameter specs and core transformer layers (norms, RoPE, attention, MLP).

All modules are pure functions over dict pytrees. Every parameter is
declared through a ``ParamSpec`` carrying its logical sharding axes, so
``init_params`` / ``axes_of`` / shardings always agree by construction.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.sharding import logical

__all__ = ["ParamSpec", "init_tree", "axes_of", "shapes_of",
           "rms_norm", "rope", "attention_specs", "mla_specs",
           "attention_apply",
           "attention_decode_paged", "mlp_specs", "mlp_apply", "ffn", "KVCache",
           "softcap"]

PyTree = Any


class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones
    scale: float = 1.0        # stddev multiplier for normal init


def _is_spec(v) -> bool:
    return isinstance(v, ParamSpec)


def init_tree(key: jax.Array, specs: PyTree, dtype=jnp.float32) -> PyTree:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    vals = []
    for k, s in zip(keys, leaves):
        if s.init == "zeros":
            vals.append(jnp.zeros(s.shape, dtype))
        elif s.init == "ones":
            vals.append(jnp.ones(s.shape, dtype))
        elif s.init == "half":
            vals.append(jnp.full(s.shape, 0.5, dtype))
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            std = s.scale / math.sqrt(max(fan_in, 1))
            vals.append((std * jax.random.normal(k, s.shape)).astype(dtype))
    return jax.tree.unflatten(treedef, vals)


def axes_of(specs: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_spec)


def shapes_of(specs: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.shape, specs, is_leaf=_is_spec)


# --------------------------------------------------------------------------
# Elementary ops
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             plus_one: bool = False) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    x32 = x32 * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if plus_one:
        w = 1.0 + w
    return (x32 * w).astype(dt)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 soft capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         fraction: float = 1.0) -> jax.Array:
    """Rotary embedding on the first ``fraction`` of the head dim.

    x: (b, s, heads, head_dim); positions: (b, s) int32.
    ``fraction=0.5`` reproduces ChatGLM's half-rotary ("2d") scheme.
    """
    head_dim = x.shape[-1]
    rot = int(head_dim * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array  # (b, max_seq, kv_heads, head_dim)
    v: jax.Array


def attention_specs(cfg: ModelConfig, cross: bool = False) -> Dict[str, ParamSpec]:
    """Projections use the fused (d_model, heads*head_dim) layout so the
    output dim shards over the model axis even when n_heads itself is not
    divisible by it (40 heads on 16-way TP -> 5120 columns shard fine);
    GSPMD then picks the attention-math sharding by propagation."""
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    specs = {
        "wq": ParamSpec((d, h * hd), ("embed", "heads_flat")),
        "wk": ParamSpec((d, kv * hd), ("embed", "kv_flat")),
        "wv": ParamSpec((d, kv * hd), ("embed", "kv_flat")),
        "wo": ParamSpec((h * hd, d), ("heads_flat", "embed")),
        "norm": ParamSpec((d,), ("embed",),
                          "zeros" if cfg.post_block_norm else "ones"),
    }
    if cfg.qkv_bias and not cross:
        specs["bq"] = ParamSpec((h * hd,), ("heads_flat",), "zeros")
        specs["bk"] = ParamSpec((kv * hd,), ("kv_flat",), "zeros")
        specs["bv"] = ParamSpec((kv * hd,), ("kv_flat",), "zeros")
    if cfg.post_block_norm:
        specs["post_norm"] = ParamSpec((d,), ("embed",), "zeros")
    return specs


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
          q_positions: jax.Array, kv_positions: jax.Array,
          causal: bool, window: Optional[int],
          softcap_val: Optional[float],
          kv_valid_len: Optional[jax.Array] = None) -> jax.Array:
    """Grouped-query scaled dot-product attention.

    q: (b, sq, h, hd); k: (b, skv, kv, hd); v: (b, skv, kv, hd_v), where
    latent attention's value heads are narrower than its q/k heads.
    positions give absolute token indices for masking (decode:
    q_position = current pos).
    """
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    group = h // kvh
    q = q.reshape(b, sq, kvh, group, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) / math.sqrt(hd)
    scores = softcap(scores.astype(jnp.float32), softcap_val)

    mask = jnp.ones((b, sq, k.shape[1]), bool)
    if causal:
        mask &= kv_positions[:, None, :] <= q_positions[:, :, None]
    if window is not None:
        mask &= kv_positions[:, None, :] > q_positions[:, :, None] - window
    if kv_valid_len is not None:
        mask &= kv_positions[:, None, :] < kv_valid_len[:, None, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  q_positions: jax.Array, kv_positions: jax.Array,
                  causal: bool, window: Optional[int],
                  softcap_val: Optional[float],
                  kv_valid_len: Optional[jax.Array],
                  chunk: int, remat: bool = False) -> jax.Array:
    """Query-chunked attention: scans q in blocks so the (sq, skv) score
    matrix never materializes whole. XLA analogue of the Pallas flash
    kernel (used where Pallas cannot lower, e.g. CPU dry-runs). With
    ``remat`` the backward pass recomputes each block's scores instead of
    keeping every block's probabilities (h * sq * skv of them)."""
    b, sq, h, hd = q.shape
    n_chunks = sq // chunk
    assert sq % chunk == 0, (sq, chunk)
    qc = q.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pc = q_positions.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def body(_, qp):
        q_i, pos_i = qp
        out = _sdpa(q_i, k, v, q_positions=pos_i, kv_positions=kv_positions,
                    causal=causal, window=window, softcap_val=softcap_val,
                    kv_valid_len=kv_valid_len)
        return None, out

    if remat:
        body = jax.checkpoint(body)
    _, outs = jax.lax.scan(body, None, (qc, pc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, v.shape[-1])


def _attend(q, k, v, *, chunk_q: Optional[int] = None, remat: bool = False,
            **kw) -> jax.Array:
    sq = q.shape[1]
    if chunk_q is not None and sq > chunk_q and sq % chunk_q == 0:
        return _sdpa_chunked(q, k, v, chunk=chunk_q, remat=remat, **kw)
    return _sdpa(q, k, v, **kw)


def _project_qkv(params: Dict[str, jax.Array], cfg: ModelConfig,
                 x: jax.Array, *, positions: jax.Array,
                 kv_source: Optional[jax.Array] = None,
                 use_rope: bool = True):
    """Shared pre-attention stage: norm, fused projections, head split,
    RoPE. Returns (residual, q, k, v) with q: (b, s, h, hd) and
    k/v: (b, skv, kv, hd)."""
    residual = x
    h = rms_norm(x, params["norm"], cfg.norm_eps,
                 plus_one=cfg.post_block_norm)
    h = logical(h, "batch", "seq", "embed")

    kv_in = kv_source if kv_source is not None else h
    n_heads = params["wq"].shape[1] // cfg.resolved_head_dim
    n_kv = params["wk"].shape[1] // cfg.resolved_head_dim
    q = jnp.einsum("bsd,de->bse", h, params["wq"])
    k = jnp.einsum("bsd,de->bse", kv_in, params["wk"])
    v = jnp.einsum("bsd,de->bse", kv_in, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = logical(q, "batch", "seq", "heads_flat")
    k = logical(k, "batch", "seq", "kv_flat")
    v = logical(v, "batch", "seq", "kv_flat")
    hd = cfg.resolved_head_dim
    q = q.reshape(*q.shape[:2], n_heads, hd)
    k = k.reshape(*k.shape[:2], n_kv, hd)
    v = v.reshape(*v.shape[:2], n_kv, hd)

    if use_rope and kv_source is None and cfg.pos_embedding == "rope":
        q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return residual, q, k, v


def mla_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    """Latent attention (DeepSeek-V3, no q compression): ``wq`` d_model ->
    heads x (nope | rope); ``wkv_a`` d_model -> the kv latent | one rope
    key shared by every head; ``wkv_b`` the normed latent -> heads x
    (k nope | v)."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return {
        "wq": ParamSpec((d, h * cfg.mla_qk_head_dim), ("embed", "heads_flat")),
        "wkv_a": ParamSpec((d, r + cfg.qk_rope_head_dim), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), "ones"),
        "wkv_b": ParamSpec((r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                           (None, "heads_flat")),
        "wo": ParamSpec((h * cfg.v_head_dim, d), ("heads_flat", "embed")),
        "norm": ParamSpec((d,), ("embed",), "ones"),
    }


# the kv latent's RMSNorm keeps the release's default epsilon, not the
# model's rms_norm_eps (DeepseekV3RMSNorm(kv_lora_rank))
MLA_LATENT_EPS = 1e-6


def _project_mla(params: Dict[str, jax.Array], cfg: ModelConfig,
                 x: jax.Array, *, positions: jax.Array):
    """Latent attention's pre-attention stage. Returns (residual, q, k, v)
    with q, k: (b, s, h, nope + rope) and v: (b, s, h, v_head_dim); the
    rope key is computed once and broadcast to every head."""
    residual = x
    hn = rms_norm(x, params["norm"], cfg.norm_eps)
    hn = logical(hn, "batch", "seq", "embed")
    b, s, _ = x.shape
    h, nope, rd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,de->bse", hn, params["wq"])
    q = logical(q, "batch", "seq", "heads_flat").reshape(b, s, h, nope + rd)
    ckv = jnp.einsum("bsd,de->bse", hn, params["wkv_a"])
    latent, k_rot = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    latent = rms_norm(latent, params["kv_norm"], MLA_LATENT_EPS)
    kv = jnp.einsum("bsr,re->bse", latent, params["wkv_b"])
    kv = logical(kv, "batch", "seq", "heads_flat").reshape(
        b, s, h, nope + cfg.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rot = rope(q[..., nope:], positions, cfg.rope_theta)
    k_rot = rope(k_rot[:, :, None, :], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rot, (b, s, h, rd))], axis=-1)
    return residual, q, k, v


def _project_out(params: Dict[str, jax.Array], cfg: ModelConfig,
                 out: jax.Array, residual: jax.Array) -> jax.Array:
    """Shared post-attention stage: head merge, output projection,
    optional post-block norm, residual add."""
    out = out.reshape(*out.shape[:2], -1)
    out = logical(out, "batch", "seq", "heads_flat")
    out = jnp.einsum("bse,ed->bsd", out, params["wo"])
    out = logical(out, "batch", "seq", "embed")
    if cfg.post_block_norm:
        out = rms_norm(out, params["post_norm"], cfg.norm_eps, plus_one=True)
    return residual + out


def attention_apply(params: Dict[str, jax.Array], cfg: ModelConfig,
                    x: jax.Array, *,
                    positions: jax.Array,
                    layer_kind: str = "attn",
                    cache: Optional[KVCache] = None,
                    cache_offset: Optional[jax.Array] = None,
                    cache_offsets: Optional[jax.Array] = None,
                    kv_source: Optional[jax.Array] = None,
                    causal: bool = True,
                    use_rope: bool = True,
                    ) -> Tuple[jax.Array, Optional[KVCache]]:
    """Self- or cross-attention with optional KV cache.

    Train/prefill: ``cache is None`` (prefill builds and returns a fresh
    cache when ``cache_offset`` is not None... see transformer.py).
    Decode: pass ``cache`` + ``cache_offset`` (current length); x has sq=1.
    Ragged decode: pass ``cache_offsets`` (b,) instead — each row writes
    its token at its OWN next position and attends only its own valid
    prefix, so right-padded unequal-length prompts stay exact.
    Cross-attention: pass ``kv_source`` (encoder / image states).
    Latent attention (``layer_kind="mla"``, self-attention only) caches
    the per-head k and v it expands from its latent; the caller names it
    with the ``mla_attn`` scope.
    """
    if layer_kind == "mla":
        residual, q, k, v = _project_mla(params, cfg, x, positions=positions)
    else:
        residual, q, k, v = _project_qkv(params, cfg, x, positions=positions,
                                         kv_source=kv_source,
                                         use_rope=use_rope)

    window = cfg.sliding_window if layer_kind == "attn_local" else None
    new_cache = None
    if kv_source is not None:
        # cross-attention: keys/values span the full encoder sequence.
        skv = k.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(skv), (x.shape[0], skv))
        out = _attend(q, k, v, chunk_q=cfg.attn_chunk_q,
                      q_positions=positions, kv_positions=kv_pos,
                      causal=False, window=None,
                      softcap_val=cfg.attn_softcap, kv_valid_len=None)
    elif cache is None:
        kv_pos = positions
        out = _attend(q, k, v, chunk_q=cfg.attn_chunk_q, remat=cfg.remat,
                      q_positions=positions, kv_positions=kv_pos,
                      causal=causal, window=window,
                      softcap_val=cfg.attn_softcap, kv_valid_len=None)
    else:
        # decode: insert this step's k/v, attend over the cache.
        b, max_seq = cache.k.shape[0], cache.k.shape[1]
        if cache_offsets is not None:
            # ragged path: row i writes at its own offset and sees only
            # its own offsets[i]+1 valid positions (sq == 1 here).
            rows = jnp.arange(b)
            k_cache = cache.k.at[rows, cache_offsets].set(
                k[:, 0].astype(cache.k.dtype))
            v_cache = cache.v.at[rows, cache_offsets].set(
                v[:, 0].astype(cache.v.dtype))
            valid = cache_offsets + 1
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), cache_offset, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), cache_offset, axis=1)
            valid = jnp.full((b,), cache_offset + x.shape[1])
        new_cache = KVCache(k_cache, v_cache)
        kv_pos = jnp.broadcast_to(jnp.arange(max_seq), (b, max_seq))
        out = _attend(q, k_cache, v_cache, chunk_q=cfg.attn_chunk_q,
                      q_positions=positions, kv_positions=kv_pos,
                      causal=True, window=window,
                      softcap_val=cfg.attn_softcap, kv_valid_len=valid)

    return _project_out(params, cfg, out, residual), new_cache


def attention_decode_paged(params: Dict[str, jax.Array], cfg: ModelConfig,
                           x: jax.Array, *,
                           pages: Tuple[jax.Array, jax.Array],
                           block_table: jax.Array,
                           offsets: jax.Array,
                           write_enabled: jax.Array,
                           layer_kind: str = "attn",
                           use_flash: bool = False,
                           ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token self-attention over a PAGED KV cache.

    x: (b, 1, d). ``pages`` is this layer's (k_pages, v_pages), each
    (n_pages, kv_heads, page_size, head_dim); ``block_table`` (b,
    n_blocks) maps row b's logical block j to a physical page;
    ``offsets`` (b,) is each row's next write position (tokens already
    cached); ``write_enabled`` (b,) routes finished / empty slots' writes
    to the reserved trash page 0 (see repro.serving.kv_cache) so a
    recycled page is never corrupted by a dead row.
    """
    from repro.kernels.flash_attn.decode import paged_attention

    b = x.shape[0]
    residual, q, k, v = _project_qkv(params, cfg, x,
                                     positions=offsets[:, None])
    k_pages, v_pages = pages
    page = k_pages.shape[2]
    rows = jnp.arange(b)
    blk = jnp.clip(offsets // page, 0, block_table.shape[1] - 1)
    page_id = jnp.where(write_enabled, block_table[rows, blk], 0)
    in_page = jnp.where(write_enabled, offsets % page, 0)
    # indexed view (b, kv_heads, head_dim): the split advanced indices lead
    k_pages = k_pages.at[page_id, :, in_page].set(
        k[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[page_id, :, in_page].set(
        v[:, 0].astype(v_pages.dtype))

    # a row that did not write must not read its (absent) current token
    seq_lens = offsets + write_enabled.astype(offsets.dtype)
    window = cfg.sliding_window if layer_kind == "attn_local" else None
    out = paged_attention(q[:, 0], k_pages, v_pages, block_table, seq_lens,
                          window=window, softcap=cfg.attn_softcap,
                          use_kernel=use_flash)
    return (_project_out(params, cfg, out[:, None], residual),
            (k_pages, v_pages))


# --------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / plain)
# --------------------------------------------------------------------------

def mlp_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    specs = {
        "w_up": ParamSpec((d, f), ("embed", "mlp")),
        "w_down": ParamSpec((f, d), ("mlp", "embed")),
        "norm": ParamSpec((d,), ("embed",),
                          "zeros" if cfg.post_block_norm else "ones"),
    }
    if cfg.glu:
        specs["w_gate"] = ParamSpec((d, f), ("embed", "mlp"))
    if cfg.post_block_norm:
        specs["post_norm"] = ParamSpec((d,), ("embed",), "zeros")
    return specs


def _activation(x: jax.Array, act: str) -> jax.Array:
    if act == "silu":
        return jax.nn.silu(x)
    if act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(act)


def ffn(params: Dict[str, jax.Array], cfg: ModelConfig,
        h: jax.Array) -> jax.Array:
    """The feed-forward projections of normed ``h`` (b, s, d)."""
    up = jnp.einsum("bsd,df->bsf", h, params["w_up"])
    if cfg.glu:
        gate = _activation(jnp.einsum("bsd,df->bsf", h, params["w_gate"]),
                           cfg.act)
        up = gate * up
    else:
        up = _activation(up, cfg.act)
    up = logical(up, "batch", "seq", "mlp")
    out = jnp.einsum("bsf,fd->bsd", up, params["w_down"])
    return logical(out, "batch", "seq", "embed")


def mlp_apply(params: Dict[str, jax.Array], cfg: ModelConfig,
              x: jax.Array) -> jax.Array:
    residual = x
    h = rms_norm(x, params["norm"], cfg.norm_eps, plus_one=cfg.post_block_norm)
    h = logical(h, "batch", "seq", "embed")
    out = ffn(params, cfg, h)
    if cfg.post_block_norm:
        out = rms_norm(out, params["post_norm"], cfg.norm_eps, plus_one=True)
    return residual + out
