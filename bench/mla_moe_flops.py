"""Operations that the per-layer shares of the latent-attention and
mixture-of-experts configurations divide by, computed from a
configuration's shapes (the ``model`` section of its file); the dense
family's are ``bench.flops``.

Model FLOPs count the matrix multiplications and the attention of the
published equations at the real (unpadded) vocabulary, the routed
experts at the held experts' share of each token's choices
(top_k * experts_held / n_experts experts a token); recomputation, the
routing's sort and permutation, the sparsifier, the noise and other
element-wise work are not counted.
"""
from __future__ import annotations


def _held(m: dict) -> int:
    return m.get("experts_held") or m["n_experts"]


def _layers(m: dict) -> list:
    """The ``ffn`` of every layer in order: the leading layers, then the
    periods."""
    prefix = [s["ffn"] for s in m.get("prefix", [])]
    period = [s["ffn"] for s in m["period"]]
    return prefix + period * ((m["n_layers"] - len(prefix)) // len(period))


def mla_params(m: dict) -> int:
    """Latent attention's projection weights, one layer."""
    d, h, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rd, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                    m["v_head_dim"])
    return (d * h * (nope + rd) + d * (r + rd) + r * h * (nope + vd)
            + h * vd * d)


def matmul_params(m: dict) -> float:
    """Weights that a token meets in a matrix multiplication, the routed
    experts at the held share of its top_k."""
    d, fe = m["d_model"], m["d_ff_expert"]
    expert = 3 * d * fe
    routed = m["top_k"] * _held(m) / m["n_experts"] * expert
    total = 0.0
    for ffn in _layers(m):
        total += mla_params(m)
        if ffn == "mlp":
            total += 3 * d * m["d_ff"]
        else:
            total += d * m["n_experts"] + routed
            total += m.get("n_shared_experts", 0) * expert
    return total + d * m["vocab_size"]


def attention_flops(m: dict, ctx: float) -> float:
    """Scores (q/k heads nope + rope wide) and weighted values (v heads)
    of one query over ``ctx`` keys, all layers."""
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return 2.0 * m["n_heads"] * width * ctx * m["n_layers"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (3x forward) per token of a causal sequence."""
    return 3.0 * (2.0 * matmul_params(m) + attention_flops(m, (seq + 1) / 2))


def gmm_flops_per_row(m: dict) -> float:
    """One row routed to a held expert: its three projections, forward
    and backward."""
    return 18.0 * m["d_model"] * m["d_ff_expert"]


def param_count(m: dict) -> int:
    """Every parameter the system holds, padded vocabulary included."""
    d, fe = m["d_model"], m["d_ff_expert"]
    mult = m.get("vocab_pad_multiple", 256)
    v = -(-m["vocab_size"] // mult) * mult
    total = 0
    for ffn in _layers(m):
        total += mla_params(m) + m["kv_lora_rank"] + d       # + the two norms
        if ffn == "mlp":
            total += 3 * d * m["d_ff"] + d
        else:
            total += d * m["n_experts"] + 3 * d * fe * _held(m) + d
            total += 3 * d * fe * m.get("n_shared_experts", 0)
    heads = 1 if m.get("tie_embeddings", True) else 2
    return total + heads * v * d + d
