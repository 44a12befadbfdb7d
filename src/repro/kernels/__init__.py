"""Pallas TPU kernels: the fused QSGD wire packer (``wire_compress``,
behind the ``qsgdf`` compressor), paged flash decode and flash prefill
(``flash_attn``), and a fused SDM update (``sdm_update``, reached only
from tests). Fixed-k payloads are packed by XLA's gather, not a kernel.

Each kernel ships as <name>/<name>.py (pl.pallas_call + BlockSpec),
<name>/ops.py (jit'd public wrapper), <name>/ref.py (pure-jnp oracle).
Kernels target TPU. Every entry point takes ``interpret=None``, which
``resolve_interpret`` turns into "compiled on a TPU backend, interpreted
everywhere else" — the CPU test suite validates the same kernel bodies
in interpret mode.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
