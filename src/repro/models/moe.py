"""Mixture-of-Experts layer: a router over every expert, and a dropless
grouped matmul over the experts this chip holds.

The router scores all ``n_experts`` at the published width, in float32:

* ``softmax``: the top_k of softmax(x W_r), renormalised to sum to 1,
  plus the Switch load-balance auxiliary loss;
* ``sigmoid`` (DeepSeek-V3's noaux_tc with one group): the top_k of
  sigmoid(x W_r) + e_score_correction_bias; each chosen expert's weight
  is its raw sigmoid score, normalised over the k chosen and scaled by
  ``routed_scaling_factor``. No auxiliary loss.

This chip holds experts [expert_offset, expert_offset + experts_held),
stacked on a leading axis. Every (token, choice) routed to a held expert
is a row: the rows are sorted by expert and run through one grouped
matmul per projection (``jax.lax.ragged_dot``) in a buffer sized for the
worst case, t * top_k rows, so no token is ever dropped. What the
absent experts would add is left out here; under expert parallelism the
chips that hold them add it. The weights are normalised over all k
chosen experts, held or not, as the uncut layer does. Shared experts (one
SwiGLU ``n_shared_experts * d_ff_expert`` wide) see every token.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import ParamSpec, rms_norm, _activation, ffn
from repro.sharding import logical

__all__ = ["moe_specs", "moe_apply", "route", "score_correction_bias",
           "layer_counts"]

# Trace-time record of the MoE layers built into programs: ``layers``
# counts traced MoE layer bodies (a scanned period's slot once),
# ``experts_held`` / ``experts_routed`` add up their held and routed
# experts, ``gmm_calls`` the grouped matmuls of their forward passes.
# Read with ``layer_counts``.
_COUNTS = {"layers": 0, "experts_held": 0, "experts_routed": 0,
           "gmm_calls": 0}


def layer_counts() -> dict:
    """The MoE layers traced so far in this process (see ``_COUNTS``).
    A reading taken before and after lowering a step says what that
    step holds. The dict is live; copy it to keep a reading."""
    return _COUNTS


def moe_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, fe, e = cfg.d_model, cfg.d_ff_expert, cfg.n_held_experts
    specs = {
        "router": ParamSpec((d, cfg.n_experts), ("embed", None)),
        "w_up": ParamSpec((e, d, fe), ("experts", "embed", "mlp")),
        "w_down": ParamSpec((e, fe, d), ("experts", "mlp", "embed")),
        "norm": ParamSpec((d,), ("embed",),
                          "zeros" if cfg.post_block_norm else "ones"),
    }
    if cfg.glu:
        specs["w_gate"] = ParamSpec((e, d, fe), ("experts", "embed", "mlp"))
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * fe
        specs["shared"] = {
            "w_up": ParamSpec((d, fs), ("embed", "mlp")),
            "w_gate": ParamSpec((d, fs), ("embed", "mlp")),
            "w_down": ParamSpec((fs, d), ("mlp", "embed")),
        }
    if cfg.post_block_norm:
        specs["post_norm"] = ParamSpec((d,), ("embed",), "zeros")
    return specs


def score_correction_bias(cfg: ModelConfig) -> jax.Array:
    """The sigmoid router's ``e_score_correction_bias``: a buffer of the
    release, not a parameter. It is held at zero and never trained, so
    it is a constant of the program and never part of the parameter
    tree (inside it, the SDM noise would random-walk it)."""
    return jnp.zeros((cfg.n_experts,), jnp.float32)


def route(cfg: ModelConfig, xt: jax.Array, router: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(weights (t, k) f32, expert ids (t, k) int32, aux loss) of the
    tokens ``xt`` (t, d) over all ``n_experts``."""
    e, k = cfg.n_experts, cfg.top_k
    # float32 at full precision, as published: a rounded score flips
    # near-tied choices
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + score_correction_bias(cfg), k)
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * cfg.routed_scaling_factor, ids, jnp.zeros((), jnp.float32)
    if cfg.router_scoring != "softmax":
        raise ValueError(cfg.router_scoring)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # Switch-style aux loss: e * sum_e fraction_tokens_e * mean_prob_e.
    onehot = jax.nn.one_hot(ids[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(onehot, axis=0) * jnp.mean(probs, axis=0))
    return w, ids, aux


def moe_apply(params: Dict[str, jax.Array], cfg: ModelConfig,
              x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (output, aux loss, rows): x (b, s, d); ``rows`` counts the
    (token, choice) pairs routed to the held experts."""
    b, s, d = x.shape
    residual = x
    h = rms_norm(x, params["norm"], cfg.norm_eps, plus_one=cfg.post_block_norm)
    h = logical(h, "batch", "seq", "embed")
    t, k, held = b * s, cfg.top_k, cfg.n_held_experts
    xt = h.reshape(t, d)
    _COUNTS["layers"] += 1
    _COUNTS["experts_held"] += held
    _COUNTS["experts_routed"] += cfg.n_experts

    with jax.named_scope("moe_route"):
        w, ids, aux = route(cfg, xt, params["router"])
        local = ids.reshape(-1) - cfg.expert_offset            # (t*k,)
        mine = (local >= 0) & (local < held)
        # rows of absent experts sort last, after every held expert's
        group = jnp.where(mine, local, held)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.sum(group[:, None] == jnp.arange(held)[None], axis=0,
                        dtype=jnp.int32)
        kept = jnp.take(mine, order)[:, None]
        # a row past the held experts' groups stays zero both ways
        xs = jnp.where(kept, jnp.take(xt, order // k, axis=0), 0)

    with jax.named_scope("moe_experts"):
        up = jax.lax.ragged_dot(xs, params["w_up"], sizes)
        if cfg.glu:
            up = _activation(jax.lax.ragged_dot(xs, params["w_gate"], sizes),
                             cfg.act) * up
        else:
            up = _activation(up, cfg.act)
        ys = jax.lax.ragged_dot(up, params["w_down"], sizes)
        _COUNTS["gmm_calls"] += 3 if cfg.glu else 2

    with jax.named_scope("moe_route"):
        ys = jnp.where(kept, ys, 0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = jnp.take(ys, back, axis=0).reshape(t, k, d)
        out = jnp.einsum("tkd,tk->td", y, jnp.where(mine.reshape(t, k), w, 0),
                         preferred_element_type=jnp.float32).astype(x.dtype)
    out = out.reshape(b, s, d)
    if cfg.n_shared_experts:
        out = out + ffn(params["shared"], cfg, h)
    out = logical(out, "batch", "seq", "embed")
    if cfg.post_block_norm:
        out = rms_norm(out, params["post_norm"], cfg.norm_eps, plus_one=True)
    return residual + out, aux.astype(jnp.float32), jnp.sum(sizes)
