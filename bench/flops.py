"""Operations and bytes that the per-layer shares divide by, computed from
a configuration's shapes (the ``model`` section of its file).

Model FLOPs count the matrix multiplications and the attention of the
published equations, at the real (unpadded) vocabulary; recomputation,
the sparsifier, the noise and other element-wise work are not counted.
"""
from __future__ import annotations

import math

from bench.drivers.sdm_reference import LANE, num_kept


def _hd(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def matmul_params(m: dict) -> int:
    """Weights that a token meets in a matrix multiplication."""
    d, f, hd = m["d_model"], m["d_ff"], _hd(m)
    h, kv = m["n_heads"], m["n_kv_heads"]
    per_layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f
    return m["n_layers"] * per_layer + d * m["vocab_size"]


def attention_flops(m: dict, ctx: float) -> float:
    """Scores and weighted values of one query over ``ctx`` keys, all
    layers."""
    return 4.0 * m["n_heads"] * _hd(m) * ctx * m["n_layers"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (3x forward) per token of a causal sequence."""
    return 3.0 * (2.0 * matmul_params(m) + attention_flops(m, (seq + 1) / 2))


def param_count(m: dict) -> int:
    """Every parameter the system holds, padded vocabulary included."""
    d, f, hd, n = m["d_model"], m["d_ff"], _hd(m), m["n_layers"]
    h, kv = m["n_heads"], m["n_kv_heads"]
    mult = m.get("vocab_pad_multiple", 256)
    v = -(-m["vocab_size"] // mult) * mult
    layer = d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f + 2 * d
    if m.get("qkv_bias", False):
        layer += (h + 2 * kv) * hd
    heads = 1 if m.get("tie_embeddings", True) else 2
    return n * layer + heads * v * d + d


def fixedk_pack(m: dict, job: dict):
    """Kept rows and bytes of one fused fixed-k pack call (the sender's
    gather of whole 128-lane f32 rows and its scaled write), or None
    where the block is not a whole number of rows."""
    block = job["block"]
    if block % LANE:
        return None
    rows = math.ceil(param_count(m) / LANE)
    nb = rows * LANE // block
    kb = num_kept(nb, job["p"])
    return {"kept_blocks": kb, "block": block,
            "bytes": kb * block * 4 * 2 + kb * 4}
