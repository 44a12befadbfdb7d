"""moonlight-16b-a3b [moe]: 27L d_model=2048, DeepSeek-V3 block: latent
attention (16 heads, kv_lora_rank 512, q/k heads 128 nope + 64 rope, v
heads 128), a dense SwiGLU (11264) first layer, then 64 routed experts
(1408 wide, top-6, sigmoid router with bias-corrected choice,
normalised weights scaled by 2.446) and 2 shared experts per layer;
vocab=163840, untied head. [hf:moonshotai/Moonlight-16B-A3B]
"""
from repro.models.config import LayerSpec, ModelConfig

_PREFIX = (LayerSpec(mixer="mla", ffn="mlp"),)
_PERIOD = (LayerSpec(mixer="mla", ffn="moe"),)
_MLA = dict(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128)
_ROUTER = dict(router_scoring="sigmoid", routed_scaling_factor=2.446)


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
        d_ff=11_264, vocab_size=163_840,
        prefix=_PREFIX, period=_PERIOD, **_MLA,
        n_experts=64, top_k=6, d_ff_expert=1408, n_shared_experts=2,
        **_ROUTER,
        rope_theta=50_000.0, norm_eps=1e-5, tie_embeddings=False,
        max_position_embeddings=8192, attn_chunk_q=1024,
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=128, vocab_size=512,
        prefix=_PREFIX, period=_PERIOD,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        n_experts=16, top_k=4, d_ff_expert=32, n_shared_experts=2,
        experts_held=8, **_ROUTER,
        rope_theta=50_000.0, norm_eps=1e-5, tie_embeddings=False,
        vocab_pad_multiple=16,
    )
