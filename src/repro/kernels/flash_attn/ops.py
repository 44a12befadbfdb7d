"""Public GQA-aware wrapper over the flash attention kernel.

Accepts the model's (b, s, h, dh) / (b, s, kv, dh) layout, repeats KV
heads for GQA, pads head_dim to a 128 multiple (MXU lane width), and
dispatches to the Pallas kernel (or the dense oracle with
``use_kernel=False``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.flash_attn import flash_attention_pallas
from repro.kernels.flash_attn.ref import attention_ref


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None, block_q: int = 128,
                    block_k: int = 128, use_kernel: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """q: (b, sq, h, dh); k/v: (b, skv, kv_heads, dh) -> (b, sq, h, dh)."""
    b, sq, h, dh = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    group = h // kvh
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    to_bh = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, t.shape[1], dh)
    qf, kf, vf = to_bh(q), to_bh(k), to_bh(v)

    pad_d = (-dh) % 128
    if use_kernel and pad_d:
        padd = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad_d)))
        # zero-padding head_dim changes q.k by nothing; rescale the softmax
        # scale to account for the padded dh used inside the kernel.
        scale_fix = ((dh + pad_d) / dh) ** 0.5
        qf = padd(qf) * scale_fix
        kf, vf = padd(kf), padd(vf)

    if use_kernel:
        out = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                     softcap=softcap, block_q=block_q,
                                     block_k=block_k, interpret=interpret)
        out = out[..., :dh]
    else:
        out = attention_ref(qf, kf, vf, causal=causal, window=window,
                            softcap=softcap)
    return out.reshape(b, h, sq, dh).transpose(0, 2, 1, 3)
