"""Model composition: embeddings + scanned layer periods + heads.

The layer stack is expressed as a repeating *period* of LayerSpecs
(config.py), after any leading layers (``prefix``; DeepSeek-V3's dense
first layer), which run once each. Parameters for each slot in the
period are stacked over a leading ``layers`` axis (n_periods entries)
and the whole stack runs under one ``jax.lax.scan`` — a single compiled
layer body regardless of depth, which keeps HLO small at 64 layers /
512 devices.

Supports: train forward, prefill (builds caches), single-token decode.
Encoder-decoder (whisper) and VLM cross-attention take pre-computed
``context`` embeddings (the modality frontends are stubs per the brief).
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import (KVCache, ParamSpec, attention_apply,
                                 attention_decode_paged, attention_specs,
                                 axes_of, init_tree, mla_specs, mlp_apply,
                                 mlp_specs, rms_norm, shapes_of, softcap)
from repro.sharding import logical

__all__ = ["model_specs", "init_params", "param_axes", "param_shapes",
           "forward", "forward_and_rows", "lm_loss", "init_cache", "prefill",
           "decode_step", "decode_step_paged", "Cache"]

PyTree = Any


# --------------------------------------------------------------------------
# Parameter specs
# --------------------------------------------------------------------------

def _slot_specs(cfg: ModelConfig, spec: LayerSpec) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    if spec.mixer in ("attn", "attn_local"):
        out["attn"] = attention_specs(cfg)
    elif spec.mixer == "mla":
        out["attn"] = mla_specs(cfg)
    elif spec.mixer == "mamba":
        out["mamba"] = mamba_mod.mamba_specs(cfg)
    elif spec.mixer == "rwkv":
        out["time_mix"] = rwkv_mod.rwkv_time_mix_specs(cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.cross_attn:
        out["cross"] = attention_specs(cfg, cross=True)
    if spec.ffn == "mlp":
        out["mlp"] = mlp_specs(cfg)
    elif spec.ffn == "moe":
        out["moe"] = moe_mod.moe_specs(cfg)
    elif spec.ffn == "rwkv_ffn":
        out["channel_mix"] = rwkv_mod.rwkv_channel_mix_specs(cfg)
    elif spec.ffn is not None:
        raise ValueError(spec.ffn)
    return out


def _stack_specs(specs: PyTree, n: int) -> PyTree:
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init,
                            s.scale),
        specs, is_leaf=lambda v: isinstance(v, ParamSpec))


def model_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.padded_vocab, d), ("vocab", "embed")),
        "final_norm": ParamSpec((d,), ("embed",), "ones"),
        "blocks": {
            str(i): _stack_specs(_slot_specs(cfg, s), cfg.n_periods)
            for i, s in enumerate(cfg.period)
        },
    }
    if cfg.prefix:
        specs["prefix"] = {str(i): _slot_specs(cfg, s)
                           for i, s in enumerate(cfg.prefix)}
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((d, cfg.padded_vocab), ("embed", "vocab"))
    if cfg.pos_embedding == "learned":
        specs["pos_embed"] = ParamSpec(
            (cfg.max_position_embeddings, d), (None, "embed"), scale=0.02)
    if cfg.has_encoder:
        enc_layer = {
            "attn": attention_specs(cfg),
            "mlp": mlp_specs(cfg),
        }
        specs["encoder"] = {
            "layers": _stack_specs(enc_layer, cfg.n_encoder_layers),
            "final_norm": ParamSpec((d,), ("embed",), "ones"),
        }
    return specs


def init_params(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> PyTree:
    return init_tree(key, model_specs(cfg), dtype)


def param_axes(cfg: ModelConfig) -> PyTree:
    return axes_of(model_specs(cfg))


def param_shapes(cfg: ModelConfig) -> PyTree:
    return shapes_of(model_specs(cfg))


# --------------------------------------------------------------------------
# Caches
# --------------------------------------------------------------------------

class Cache(NamedTuple):
    """Per-slot caches, each stacked over the period axis (n_periods, ...),
    and the leading layers' caches (``prefix``, unstacked)."""
    slots: Dict[str, Any]
    offset: jax.Array  # () int32 — number of tokens already in the cache
    prefix: Dict[str, Any] = {}


def _slot_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_len: int,
                dtype, lead: Tuple[int, ...]) -> Any:
    if spec.mixer in ("attn", "attn_local"):
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        shape = lead + (batch, max_len, kv, hd)
        return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
    if spec.mixer == "mla":
        # per-head k and v, expanded from the latent
        shape = lead + (batch, max_len, cfg.n_heads)
        return KVCache(k=jnp.zeros(shape + (cfg.mla_qk_head_dim,), dtype),
                       v=jnp.zeros(shape + (cfg.v_head_dim,), dtype))
    if spec.mixer == "mamba":
        st = mamba_mod.init_mamba_state(cfg, batch, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, lead + a.shape), st)
    if spec.mixer == "rwkv":
        st = rwkv_mod.init_rwkv_state(cfg, batch, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, lead + a.shape), st)
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Cache:
    return Cache(
        slots={str(i): _slot_cache(cfg, s, batch, max_len, dtype,
                                   (cfg.n_periods,))
               for i, s in enumerate(cfg.period)},
        offset=jnp.zeros((), jnp.int32),
        prefix={str(i): _slot_cache(cfg, s, batch, max_len, dtype, ())
                for i, s in enumerate(cfg.prefix)})


def cache_logical_axes(cfg: ModelConfig) -> Cache:
    """Logical axes tree matching init_cache's structure."""
    def slot_axes(spec: LayerSpec, lead: Tuple[str, ...]):
        if spec.mixer in ("attn", "attn_local", "mla"):
            a = lead + ("batch", "cache_seq", "kv_heads", None)
            return KVCache(k=a, v=a)
        if spec.mixer == "mamba":
            return mamba_mod.MambaState(
                conv=lead + ("batch", None, "mlp"),
                ssm=lead + ("batch", "mlp", None))
        if spec.mixer == "rwkv":
            return rwkv_mod.RWKVState(
                att_shift=lead + ("batch", "embed"),
                ffn_shift=lead + ("batch", "embed"),
                wkv=lead + ("batch", "heads", None, None))
        raise ValueError(spec.mixer)

    return Cache(slots={str(i): slot_axes(s, ("layers",))
                        for i, s in enumerate(cfg.period)},
                 offset=(),
                 prefix={str(i): slot_axes(s, ())
                         for i, s in enumerate(cfg.prefix)})


# --------------------------------------------------------------------------
# Forward passes
# --------------------------------------------------------------------------

def _embed_tokens(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return logical(x, "batch", "seq", "embed")


def _logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head)
    logits = softcap(logits, cfg.logit_softcap)
    return logical(logits, "batch", "seq", "vocab")


def encode_context(params, cfg: ModelConfig,
                   context: Optional[jax.Array]) -> Optional[jax.Array]:
    """Public: pre-encode context once for serving (see decode_step)."""
    return _encode_context(params, cfg, context)


def _encode_context(params, cfg: ModelConfig,
                    context: Optional[jax.Array]) -> Optional[jax.Array]:
    """Whisper: run the encoder stack over stub frame embeddings.
    VLM: pass the stub patch embeddings straight through."""
    if context is None or not cfg.has_encoder:
        return context
    enc = params["encoder"]
    b, s, _ = context.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, layer):
        x, _ = attention_apply(layer["attn"], cfg, x, positions=positions,
                               causal=False, use_rope=False)
        x = mlp_apply(layer["mlp"], cfg, x)
        return x, None

    if cfg.unroll_layers:
        x = context
        for i in range(cfg.n_encoder_layers):
            x, _ = body(x, jax.tree.map(lambda v: v[i], enc["layers"]))
    else:
        x, _ = jax.lax.scan(body, context, enc["layers"])
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def _mixer_scope(spec: LayerSpec):
    """Latent attention runs under the ``mla_attn`` span."""
    if spec.mixer == "mla":
        return jax.named_scope("mla_attn")
    return contextlib.nullcontext()


def _apply_slot_full(cfg: ModelConfig, spec: LayerSpec, slot_params,
                     x: jax.Array, positions: jax.Array,
                     context: Optional[jax.Array],
                     init_state, want_state: bool):
    """One layer slot over a full sequence. Returns (x, aux, rows,
    new_state); ``rows`` counts an MoE layer's rows routed to held
    experts."""
    aux = jnp.zeros((), jnp.float32)
    rows = jnp.zeros((), jnp.int32)
    state = None
    if spec.mixer in ("attn", "attn_local", "mla"):
        with _mixer_scope(spec):
            if want_state:
                # prefill: write this call's k/v into the provided cache
                x, state = attention_apply(
                    slot_params["attn"], cfg, x, positions=positions,
                    layer_kind=spec.mixer, cache=init_state,
                    cache_offset=jnp.zeros((), jnp.int32))
            else:
                x, _ = attention_apply(slot_params["attn"], cfg, x,
                                       positions=positions,
                                       layer_kind=spec.mixer)
    elif spec.mixer == "mamba":
        if want_state:
            x, state = mamba_mod.mamba_apply(slot_params["mamba"], cfg, x,
                                             return_state=True)
        else:
            x = mamba_mod.mamba_apply(slot_params["mamba"], cfg, x)
    elif spec.mixer == "rwkv":
        rstate = init_state if init_state is not None else \
            rwkv_mod.init_rwkv_state(cfg, x.shape[0], x.dtype)
        x, rstate = rwkv_mod.rwkv_time_mix(slot_params["time_mix"], cfg, x,
                                           rstate)
        state = rstate

    if spec.cross_attn and context is not None:
        x, _ = attention_apply(slot_params["cross"], cfg, x,
                               positions=positions, kv_source=context)

    if spec.ffn == "mlp":
        x = mlp_apply(slot_params["mlp"], cfg, x)
    elif spec.ffn == "moe":
        x, aux, rows = moe_mod.moe_apply(slot_params["moe"], cfg, x)
    elif spec.ffn == "rwkv_ffn":
        x, state = rwkv_mod.rwkv_channel_mix(slot_params["channel_mix"], cfg,
                                             x, state)
    return x, aux, rows, state



def _scan_periods(cfg: ModelConfig, body, init_carry, xs):
    """lax.scan over stacked periods, or a python loop when
    cfg.unroll_layers (exact cost_analysis: XLA counts while-loop bodies
    once regardless of trip count, so cost probes must unroll)."""
    if not cfg.unroll_layers:
        return jax.lax.scan(body, init_carry, xs)
    carry = init_carry
    ys = []
    for i in range(cfg.n_periods):
        carry, y = body(carry, jax.tree.map(lambda v: v[i], xs))
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    stacked = jax.tree.map(lambda *vs: jnp.stack(vs, axis=0), *ys)
    return carry, stacked


def _remat(cfg: ModelConfig, fn):
    if not cfg.remat:
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def forward(params: PyTree, cfg: ModelConfig, tokens: jax.Array, *,
            context: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Training forward. tokens: (b, s) -> (logits (b, s, V), aux_loss)."""
    logits, aux, _ = forward_and_rows(params, cfg, tokens, context=context)
    return logits, aux


def forward_and_rows(params: PyTree, cfg: ModelConfig, tokens: jax.Array, *,
                     context: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``forward``, and the rows routed to held experts summed over the MoE
    layers (int32; 0 for a model without them)."""
    b, s = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if cfg.pos_embedding == "learned":
        x = x + params["pos_embed"][:s][None]
    ctx = _encode_context(params, cfg, context)

    aux = jnp.zeros((), jnp.float32)
    rows = jnp.zeros((), jnp.int32)
    for i, spec in enumerate(cfg.prefix):
        def leading(x, p, spec=spec):
            x, a, r, _ = _apply_slot_full(cfg, spec, p, x, positions, ctx,
                                          None, False)
            return x, (a, r)
        x, (a, r) = _remat(cfg, leading)(x, params["prefix"][str(i)])
        aux, rows = aux + a, rows + r

    def period_body(x, period_params):
        aux = jnp.zeros((), jnp.float32)
        rows = jnp.zeros((), jnp.int32)
        for i, spec in enumerate(cfg.period):
            x, a, r, _ = _apply_slot_full(cfg, spec, period_params[str(i)],
                                          x, positions, ctx, None, False)
            aux, rows = aux + a, rows + r
        return x, (aux, rows)

    x, (auxs, period_rows) = _scan_periods(cfg, _remat(cfg, period_body), x,
                                           params["blocks"])
    return (_logits(params, cfg, x), aux + jnp.sum(auxs),
            rows + jnp.sum(period_rows))


def lm_loss(logits: jax.Array, labels: jax.Array, vocab_size: int,
            aux: jax.Array = 0.0, aux_weight: float = 0.01) -> jax.Array:
    """Next-token cross entropy; the padded vocab tail is masked out."""
    v = logits.shape[-1]
    pad_mask = jnp.arange(v) >= vocab_size
    logits = jnp.where(pad_mask, -1e30, logits.astype(jnp.float32))
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold) + aux_weight * aux


# --------------------------------------------------------------------------
# Prefill & decode
# --------------------------------------------------------------------------

def prefill(params: PyTree, cfg: ModelConfig, tokens: jax.Array,
            cache: Cache, *, context: Optional[jax.Array] = None,
            last_index: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Cache]:
    """Process a prompt, filling ``cache``. Returns (last-token logits, cache).

    ``cache`` must be created by init_cache with max_len >= prompt + new.
    ``last_index`` (b,) selects each row's OWN last real token for the
    returned logits — required for right-padded unequal-length prompts,
    where the final column is padding for the shorter rows (causal
    masking already keeps their hidden states exact; only the readout
    position differs).
    """
    b, s = tokens.shape
    x = _embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if cfg.pos_embedding == "learned":
        x = x + params["pos_embed"][:s][None]
    ctx = _encode_context(params, cfg, context)

    def layer(x, spec, slot_params, slot_cache):
        x, _, _, st = _apply_slot_full(cfg, spec, slot_params, x, positions,
                                       ctx, slot_cache, True)
        return x, st if st is not None else slot_cache

    new_prefix = {}
    for i, spec in enumerate(cfg.prefix):
        x, new_prefix[str(i)] = layer(x, spec, params["prefix"][str(i)],
                                      cache.prefix[str(i)])

    def period_body(x, scanned):
        period_params, period_cache = scanned
        new_cache = {}
        for i, spec in enumerate(cfg.period):
            x, new_cache[str(i)] = layer(x, spec, period_params[str(i)],
                                         period_cache[str(i)])
        return x, new_cache

    x, new_slots = _scan_periods(cfg, period_body, x,
                                 (params["blocks"], cache.slots))
    if last_index is None:
        x_last = x[:, -1:, :]
    else:
        x_last = jnp.take_along_axis(x, last_index[:, None, None], axis=1)
    logits = _logits(params, cfg, x_last)
    return logits[:, 0, :], Cache(slots=new_slots,
                                  offset=jnp.asarray(s, jnp.int32),
                                  prefix=new_prefix)


def decode_step(params: PyTree, cfg: ModelConfig, token: jax.Array,
                cache: Cache, *, context: Optional[jax.Array] = None,
                offsets: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Cache]:
    """One greedy-decode step. token: (b,) int32 -> (logits (b, V), cache).

    ``context`` must be PRE-ENCODED (encode_context) — the encoder runs
    once per request, never per decoded token.
    ``offsets`` (b,) makes the step RAGGED-aware: each row writes its
    token at its own cache position, takes its own RoPE phase, and
    attends only its own valid prefix. Without it every row shares the
    scalar ``cache.offset`` (the legacy equal-length path, unchanged).
    """
    b = token.shape[0]
    x = _embed_tokens(params, cfg, token[:, None])
    if offsets is not None:
        positions = offsets[:, None]
    else:
        positions = jnp.broadcast_to(cache.offset[None, None], (b, 1))
    if cfg.pos_embedding == "learned":
        if offsets is not None:
            x = x + jnp.take(params["pos_embed"], offsets, axis=0)[:, None]
        else:
            x = x + jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], cache.offset, 1, axis=0)[None]
    ctx = context

    def layer(x, spec, sp, pc):
        if spec.mixer in ("attn", "attn_local", "mla"):
            with _mixer_scope(spec):
                x, pc = attention_apply(sp["attn"], cfg, x,
                                        positions=positions,
                                        layer_kind=spec.mixer, cache=pc,
                                        cache_offset=cache.offset,
                                        cache_offsets=offsets)
        elif spec.mixer == "mamba":
            x, pc = mamba_mod.mamba_decode_step(sp["mamba"], cfg, x, pc)
        elif spec.mixer == "rwkv":
            x, pc = rwkv_mod.rwkv_time_mix_step(sp["time_mix"], cfg, x, pc)
        if spec.cross_attn and ctx is not None:
            x, _ = attention_apply(sp["cross"], cfg, x,
                                   positions=positions, kv_source=ctx)
        if spec.ffn == "mlp":
            x = mlp_apply(sp["mlp"], cfg, x)
        elif spec.ffn == "moe":
            x, _, _ = moe_mod.moe_apply(sp["moe"], cfg, x)
        elif spec.ffn == "rwkv_ffn":
            x, pc = rwkv_mod.rwkv_channel_mix_step(sp["channel_mix"], cfg, x,
                                                   pc)
        return x, pc

    new_prefix = {}
    for i, spec in enumerate(cfg.prefix):
        x, new_prefix[str(i)] = layer(x, spec, params["prefix"][str(i)],
                                      cache.prefix[str(i)])

    def period_body(x, scanned):
        period_params, period_cache = scanned
        new_cache = {}
        for i, spec in enumerate(cfg.period):
            x, new_cache[str(i)] = layer(x, spec, period_params[str(i)],
                                         period_cache[str(i)])
        return x, new_cache

    x, new_slots = _scan_periods(cfg, period_body, x,
                                 (params["blocks"], cache.slots))
    logits = _logits(params, cfg, x)
    return logits[:, 0, :], Cache(slots=new_slots, offset=cache.offset + 1,
                                  prefix=new_prefix)


def decode_step_paged(params: PyTree, cfg: ModelConfig, token: jax.Array,
                      pages: Dict[str, Any], rec: Dict[str, Any],
                      block_tables: jax.Array, offsets: jax.Array,
                      write_enabled: jax.Array, *,
                      context: Optional[jax.Array] = None,
                      use_flash: bool = False
                      ) -> Tuple[jax.Array, Dict[str, Any], Dict[str, Any]]:
    """One decode step over a PAGED KV cache (continuous-batching engine).

    ``pages``: {period-slot index -> (k_pages, v_pages)} for attention
    slots, each array (n_periods, n_pages, kv_heads, page_size, head_dim)
    — one shared physical page pool per layer slot, scanned over the
    period axis alongside the parameters. ``rec``: {period-slot index ->
    recurrent state} for mamba/rwkv slots (dense per-row state; paging
    only applies to KV). ``block_tables`` (b, n_blocks) and ``offsets``
    (b,) are per-REQUEST-slot; ``write_enabled`` (b,) masks finished /
    empty rows so their writes land on the trash page.

    Returns (logits (b, V), new_pages, new_rec). The whole step is one
    jitted function with no host round-trips — the serving engine's
    done-mask bookkeeping composes around it on device.
    """
    if cfg.prefix or any(s.mixer == "mla" for s in cfg.period):
        raise NotImplementedError(
            f"{cfg.name}: the paged cache holds the period's attn and "
            f"attn_local layers only; leading layers and latent attention "
            f"(mla) decode through the dense cache (decode_step)")
    b = token.shape[0]
    x = _embed_tokens(params, cfg, token[:, None])
    positions = offsets[:, None]
    if cfg.pos_embedding == "learned":
        x = x + jnp.take(params["pos_embed"], offsets, axis=0)[:, None]
    ctx = context

    def period_body(x, scanned):
        period_params, period_pages, period_rec = scanned
        new_pages: Dict[str, Any] = {}
        new_rec: Dict[str, Any] = {}
        for i, spec in enumerate(cfg.period):
            si = str(i)
            sp = period_params[si]
            if spec.mixer in ("attn", "attn_local"):
                x, new_pages[si] = attention_decode_paged(
                    sp["attn"], cfg, x, pages=period_pages[si],
                    block_table=block_tables, offsets=offsets,
                    write_enabled=write_enabled, layer_kind=spec.mixer,
                    use_flash=use_flash)
            elif spec.mixer == "mamba":
                x, new_rec[si] = mamba_mod.mamba_decode_step(
                    sp["mamba"], cfg, x, period_rec[si])
            elif spec.mixer == "rwkv":
                x, new_rec[si] = rwkv_mod.rwkv_time_mix_step(
                    sp["time_mix"], cfg, x, period_rec[si])
            if spec.cross_attn and ctx is not None:
                x, _ = attention_apply(sp["cross"], cfg, x,
                                       positions=positions, kv_source=ctx)
            if spec.ffn == "mlp":
                x = mlp_apply(sp["mlp"], cfg, x)
            elif spec.ffn == "moe":
                x, _, _ = moe_mod.moe_apply(sp["moe"], cfg, x)
            elif spec.ffn == "rwkv_ffn":
                x, new_rec[si] = rwkv_mod.rwkv_channel_mix_step(
                    sp["channel_mix"], cfg, x, new_rec[si])
        return x, (new_pages, new_rec)

    x, (new_pages, new_rec) = _scan_periods(
        cfg, period_body, x, (params["blocks"], pages, rec))
    logits = _logits(params, cfg, x)
    return logits[:, 0, :], new_pages, new_rec
