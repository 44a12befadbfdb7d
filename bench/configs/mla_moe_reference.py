"""Plain reference of the DeepSeek-V3 decoder block as Moonlight-16B-A3B
publishes it: pre-norm RMSNorm blocks of latent attention (MLA) followed
by a dense SwiGLU in the leading layers and by a mixture of experts
(routed SwiGLU experts plus shared ones) in the layers after them, and an
untied head.

Written from the published equations (``DeepseekV3Attention``,
``DeepseekV3TopkRouter``, ``DeepseekV3MoE`` of transformers, and the
model's config.json), in straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision, importing nothing of the system under test:

* attention: q = h W_q, split per head into 128 "nope" and 64 rope
  columns; [c | k_rot] = h W_kv_a; [k_nope | v] = RMSNorm(c) W_kv_b per
  head; RoPE (theta 50000) on q's rope columns and on the one k_rot that
  every head shares; softmax(q.k / sqrt(192)) over the causal past, times
  v, then W_o;
* router: s = sigmoid(h W_r) in float32; the top 6 of s + bias (the
  score-correction bias, held at zero); weights = the chosen s,
  normalised to sum to 1, times the routed scaling factor;
* experts: shared(h) + sum over experts of weight * expert(h), each a
  SwiGLU, run here as a dense loop over the experts this chip holds,
  every token through every held expert, masked by its weight.

Departures from the release, shared by the system under test:

* only experts [expert_offset, expert_offset + experts_held) are held;
  what the others would add is left out, while the router still scores
  all of them and the weights are normalised over all chosen ones;
* RoPE rotates the two halves of the rope columns where the release
  interleaves pairs: a fixed permutation of those columns of W_q and
  W_kv_a on random weights;
* the score-correction bias is zero and untrained, and there is no
  auxiliary sequence-balance loss.

Parameters are drawn as the system draws them, so that both sides start
from the same weights made from the same seed: one key per leaf, in the
flatten order of the parameter tree, normal with std 1/sqrt(fan-in).
Each layer, and each block of attention queries, is recomputed in the
backward pass, so that 8,192 tokens fit one chip at float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LATENT_EPS = 1e-6       # DeepseekV3RMSNorm(kv_lora_rank)'s default
QUERY_BLOCK = 1024


def padded_vocab(m: dict) -> int:
    mult = m.get("vocab_pad_multiple", 256)
    return -(-m["vocab_size"] // mult) * mult


def n_periods(m: dict) -> int:
    return (m["n_layers"] - len(m.get("prefix", []))) // len(m["period"])


def held(m: dict) -> int:
    return m.get("experts_held") or m["n_experts"]


def _layer_specs(m: dict, ffn: str, lead: tuple) -> dict:
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rd, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                    m["v_head_dim"])
    out = {"attn": {
        "wq": (lead + (d, h * (nope + rd)), "normal"),
        "wkv_a": (lead + (d, r + rd), "normal"),
        "kv_norm": (lead + (r,), "ones"),
        "wkv_b": (lead + (r, h * (nope + vd)), "normal"),
        "wo": (lead + (h * vd, d), "normal"),
        "norm": (lead + (d,), "ones")}}
    if ffn == "mlp":
        f = m["d_ff"]
        out["mlp"] = {"w_up": (lead + (d, f), "normal"),
                      "w_gate": (lead + (d, f), "normal"),
                      "w_down": (lead + (f, d), "normal"),
                      "norm": (lead + (d,), "ones")}
    else:
        e, fe = held(m), m["d_ff_expert"]
        moe = {"router": (lead + (d, m["n_experts"]), "normal"),
               "w_up": (lead + (e, d, fe), "normal"),
               "w_gate": (lead + (e, d, fe), "normal"),
               "w_down": (lead + (e, fe, d), "normal"),
               "norm": (lead + (d,), "ones")}
        if m.get("n_shared_experts", 0):
            fs = m["n_shared_experts"] * fe
            moe["shared"] = {"w_up": (lead + (d, fs), "normal"),
                             "w_gate": (lead + (d, fs), "normal"),
                             "w_down": (lead + (fs, d), "normal")}
        out["moe"] = moe
    return out


def param_specs(m: dict) -> dict:
    """{path: (shape, init)} in the parameter tree's nesting. The
    period's leaves are stacked over a leading layer axis; the leading
    layers' are not."""
    d, v = m["d_model"], padded_vocab(m)
    tree = {"embed": ((v, d), "normal"), "final_norm": ((d,), "ones"),
            "blocks": {str(i): _layer_specs(m, s["ffn"], (n_periods(m),))
                       for i, s in enumerate(m["period"])}}
    if m.get("prefix"):
        tree["prefix"] = {str(i): _layer_specs(m, s["ffn"], ())
                          for i, s in enumerate(m["prefix"])}
    if not m.get("tie_embeddings", True):
        tree["lm_head"] = ((d, v), "normal")
    return tree


def _is_spec(v) -> bool:
    return isinstance(v, tuple) and len(v) == 2 and isinstance(v[1], str)


def init_params(key, m: dict, dtype):
    """The weights the seed's key gives, in ``dtype``."""
    leaves, treedef = jax.tree.flatten(param_specs(m), is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, init) in zip(keys, leaves):
        if init == "ones":
            out.append(jnp.ones(shape, dtype))
        else:
            std = 1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else shape[-1])
            out.append((std * jax.random.normal(k, shape)).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def _mm(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (b, s, heads, r): the two halves of the r columns rotated."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs           # (b, s, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(a, m: dict, x, pos):
    """The latent-attention sublayer's output (without the residual)."""
    b, s, _ = x.shape
    h, r = m["n_heads"], m["kv_lora_rank"]
    nope, rd = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    hn = _rms_norm(x, a["norm"], m.get("norm_eps", 1e-6))
    q = _mm("bsd,de->bse", hn, a["wq"]).reshape(b, s, h, nope + rd)
    ckv = _mm("bsd,de->bse", hn, a["wkv_a"])
    kv = _mm("bsr,re->bse", _rms_norm(ckv[..., :r], a["kv_norm"], LATENT_EPS),
             a["wkv_b"]).reshape(b, s, h, -1)
    theta = m.get("rope_theta", 10_000.0)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, theta)], -1)
    k_rot = _rope(ckv[..., None, r:], pos, theta)               # (b, s, 1, rd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rot, (b, s, h, rd))], -1)
    v = kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rd)

    @jax.checkpoint
    def block(qb, pb):
        scores = _mm("bqhd,bshd->bhqs", qb, k) * scale
        causal = pb[:, None, :, None] >= pos[:, None, None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm("bhqs,bshd->bqhd", probs, v)

    nb = max(1, s // QUERY_BLOCK) if s % QUERY_BLOCK == 0 else 1
    qs = q.reshape(b, nb, s // nb, h, nope + rd).swapaxes(0, 1)
    ps = pos.reshape(b, nb, s // nb).swapaxes(0, 1)
    o = jax.lax.map(lambda qp: block(*qp), (qs, ps))
    o = o.swapaxes(0, 1).reshape(b, s, -1)
    return _mm("bse,ed->bsd", o, a["wo"])


def _swiglu(p, hn, idx=()):
    g = _mm("bsd,df->bsf", hn, p["w_gate"][idx])
    u = _mm("bsd,df->bsf", hn, p["w_up"][idx])
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"][idx])


def route(m: dict, hn, router):
    """(weights, expert ids), each (b, s, top_k), over all experts."""
    logits = _mm("bsd,de->bse", hn, router)
    if m.get("router_scoring", "softmax") == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = jnp.zeros((m["n_experts"],), jnp.float32)
        _, ids = jax.lax.top_k(scores + bias, m["top_k"])
        w = jnp.take_along_axis(scores, ids, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * m.get("routed_scaling_factor", 1.0), ids
    w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m["top_k"])
    return w / jnp.sum(w, axis=-1, keepdims=True), ids


def moe(p, m: dict, hn):
    """The expert layer's output on normed ``hn`` (without the residual):
    the shared experts, plus each held expert's SwiGLU over every token
    weighted by that token's routing weight for it (0 if not chosen)."""
    w, ids = route(m, hn, p["router"])
    out = _swiglu(p["shared"], hn) if "shared" in p else 0.0
    off = m.get("expert_offset", 0)
    for j in range(held(m)):
        wj = jnp.sum(jnp.where(ids == off + j, w, 0.0), axis=-1)
        out = out + wj[..., None] * _swiglu(p, hn, j)
    return out


def _layer(p, m: dict, x, pos):
    eps = m.get("norm_eps", 1e-6)
    x = x + mla(p["attn"], m, x, pos)
    if "mlp" in p:
        return x + _swiglu(p["mlp"], _rms_norm(x, p["mlp"]["norm"], eps))
    return x + moe(p["moe"], m, _rms_norm(x, p["moe"]["norm"], eps))


def logits(params, m: dict, tokens):
    """(b, s) tokens -> (b, s, padded vocab) float32 logits."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = jnp.take(params["embed"], tokens, axis=0)
    layer = jax.checkpoint(lambda p, x: _layer(p, m, x, pos))
    for i in range(len(m.get("prefix", []))):
        x = layer(params["prefix"][str(i)], x)
    for t in range(n_periods(m)):
        for i in range(len(m["period"])):
            x = layer(jax.tree.map(lambda a: a[t],
                                   params["blocks"][str(i)]), x)
    x = _rms_norm(x, params["final_norm"], m.get("norm_eps", 1e-6))
    head = params["embed"].T if m.get("tie_embeddings", True) \
        else params["lm_head"]
    return _mm("bsd,dv->bsv", x, head)


def loss(params, m: dict, tokens, labels):
    """Mean next-token cross entropy over the real (unpadded) vocabulary."""
    z = logits(params, m, tokens)
    z = jnp.where(jnp.arange(z.shape[-1]) >= m["vocab_size"], -jnp.inf, z)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
