"""Plain reference of the dense decoder family: pre-norm RMSNorm blocks of
grouped-query attention with rotary embeddings (on the first
``rope_fraction`` of each head) and a SwiGLU MLP, untied or tied head.

Both benchmark configurations (phi3-medium, chatglm3) are of this
family. Everything here is straightforward ``jax.numpy`` at float32 and
``highest`` matmul precision, written from the published descriptions
and imported from nothing of the system under test. Departures from the
published models, shared by the system under test:

* the rotary embedding rotates the two halves of the rotated span
  (NeoX layout); ChatGLM interleaves adjacent pairs. On random weights
  the two differ by a fixed permutation of the q/k projection columns.
* the vocabulary is padded to a multiple of ``vocab_pad_multiple`` rows;
  padded logits are masked out of the loss.

Parameters are drawn as the system draws them, so that both sides start
from the same weights made from the same seed: one key per leaf, in the
flatten order of the parameter tree, normal with std 1/sqrt(fan-in).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def padded_vocab(m: dict) -> int:
    mult = m.get("vocab_pad_multiple", 256)
    return -(-m["vocab_size"] // mult) * mult


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def param_specs(m: dict) -> dict:
    """{path: (shape, init)} in the parameter tree's nesting. Layer
    leaves are stacked over a leading layer axis."""
    d, f, hd, n = m["d_model"], m["d_ff"], head_dim(m), m["n_layers"]
    h, kv, v = m["n_heads"], m["n_kv_heads"], padded_vocab(m)
    attn = {"wq": ((n, d, h * hd), "normal"), "wk": ((n, d, kv * hd), "normal"),
            "wv": ((n, d, kv * hd), "normal"), "wo": ((n, h * hd, d), "normal"),
            "norm": ((n, d), "ones")}
    if m.get("qkv_bias", False):
        attn.update(bq=((n, h * hd), "zeros"), bk=((n, kv * hd), "zeros"),
                    bv=((n, kv * hd), "zeros"))
    mlp = {"w_up": ((n, d, f), "normal"), "w_gate": ((n, d, f), "normal"),
           "w_down": ((n, f, d), "normal"), "norm": ((n, d), "ones")}
    tree = {"embed": ((v, d), "normal"), "final_norm": ((d,), "ones"),
            "blocks": {"0": {"attn": attn, "mlp": mlp}}}
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
        elif init == "zeros":
            out.append(jnp.zeros(shape, dtype))
        else:
            std = 1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else shape[-1])
            out.append((std * jax.random.normal(k, shape)).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta, fraction):
    rot = int(x.shape[-1] * fraction)
    rot -= rot % 2
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs          # (b, s, half)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _layer(p, m, x, pos):
    a, f = p["attn"], p["mlp"]
    b, s, _ = x.shape
    hd, h, kv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    eps = m.get("norm_eps", 1e-6)
    mm = lambda u, w: jnp.einsum("bsd,de->bse", u, w, precision=HIGHEST)
    hn = _rms_norm(x, a["norm"], eps)
    q, k, v = mm(hn, a["wq"]), mm(hn, a["wk"]), mm(hn, a["wv"])
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    theta, frac = m.get("rope_theta", 10_000.0), m.get("rope_fraction", 1.0)
    q = _rope(q.reshape(b, s, h, hd), pos, theta, frac)
    k = _rope(k.reshape(b, s, kv, hd), pos, theta, frac)
    v = v.reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        precision=HIGHEST) / math.sqrt(hd)
    causal = pos[:, None, None, :, None] >= pos[:, None, None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=HIGHEST)
    x = x + mm(o.reshape(b, s, h * hd), a["wo"])
    hn = _rms_norm(x, f["norm"], eps)
    up = jax.nn.silu(mm(hn, f["w_gate"])) * mm(hn, f["w_up"])
    return x + jnp.einsum("bsf,fd->bsd", up, f["w_down"], precision=HIGHEST)


def logits(params, m: dict, tokens):
    """(b, s) tokens -> (b, s, padded vocab) float32 logits."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = jnp.take(params["embed"], tokens, axis=0)
    blocks = params["blocks"]["0"]
    for i in range(m["n_layers"]):
        x = _layer(jax.tree.map(lambda a: a[i], blocks), m, x, pos)
    x = _rms_norm(x, params["final_norm"], m.get("norm_eps", 1e-6))
    head = params["embed"].T if m.get("tie_embeddings", True) \
        else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head, precision=HIGHEST)


def loss(params, m: dict, tokens, labels):
    """Mean next-token cross entropy over the real (unpadded) vocabulary."""
    z = logits(params, m, tokens)
    z = jnp.where(jnp.arange(z.shape[-1]) >= m["vocab_size"], -jnp.inf, z)
    logz = jax.nn.logsumexp(z, axis=-1)
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
