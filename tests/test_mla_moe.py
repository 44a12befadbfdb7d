"""Latent attention and the dropless local-experts layer against the plain
float32 reference (``bench/configs/mla_moe_reference.py``), on seeded
random weights at a small size: the sublayers, the whole model's loss
and gradients, the experts' shares summing to the uncut layer, no token
dropped under total imbalance, the router's bias kept out of the SDM
state, and the reference against transformers' DeepseekV3 itself."""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench.configs import mla_moe_reference as ref  # noqa: E402
from repro.models import moe, transformer  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.layers import attention_apply, ffn, rms_norm  # noqa: E402

M = {"name": "tiny-moe", "family": "moe", "n_layers": 3, "d_model": 64,
     "n_heads": 4, "n_kv_heads": 4, "d_ff": 128, "vocab_size": 500,
     "prefix": [{"mixer": "mla", "ffn": "mlp"}],
     "period": [{"mixer": "mla", "ffn": "moe"}],
     "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
     "v_head_dim": 16, "n_experts": 16, "top_k": 4, "d_ff_expert": 32,
     "experts_held": 8, "expert_offset": 0, "n_shared_experts": 2,
     "router_scoring": "sigmoid", "routed_scaling_factor": 2.446,
     "rope_theta": 50000.0, "norm_eps": 1e-05, "tie_embeddings": False,
     "vocab_pad_multiple": 16, "attn_chunk_q": 16}
B, S = 2, 32
TOL = dict(rtol=2e-5, atol=2e-5)      # float32 on both sides, sums reordered


def _setup(m=M, seed=0):
    cfg = ModelConfig(**m)
    params = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, m["d_model"]))
    return cfg, params, x


def _layer(params, t=0):
    return jax.tree.map(lambda a: a[t], params["blocks"]["0"])


def test_system_draws_the_references_parameters():
    cfg, params, _ = _setup()
    theirs = ref.init_params(jax.random.PRNGKey(0), M, jnp.float32)
    assert jax.tree.structure(params) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("chunk", [None, 16])
def test_mla_sublayer_matches_reference(chunk):
    cfg, params, x = _setup()
    cfg = dataclasses.replace(cfg, attn_chunk_q=chunk)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    p = params["prefix"]["0"]["attn"]
    out, _ = attention_apply(p, cfg, x, positions=pos, layer_kind="mla")
    np.testing.assert_allclose(np.asarray(out - x),
                               np.asarray(ref.mla(p, M, x, pos)), **TOL)


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
def test_moe_layer_matches_reference(router):
    m = dict(M, router_scoring=router)
    cfg, params, x = _setup(m)
    p = _layer(params)["moe"]
    out, aux, rows = moe.moe_apply(p, cfg, x)
    hn = rms_norm(x, p["norm"], cfg.norm_eps)
    np.testing.assert_allclose(np.asarray(out - x),
                               np.asarray(ref.moe(p, m, hn)), **TOL)
    _, ids = ref.route(m, hn, p["router"])
    assert int(rows) == int(jnp.sum(ids < cfg.n_held_experts))
    assert (float(aux) == 0.0) == (router == "sigmoid")


def test_loss_and_gradients_match_reference():
    cfg, params, _ = _setup()
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S + 1), 0,
                              M["vocab_size"])
    tokens, labels = toks[:, :-1], toks[:, 1:]

    def system(p):
        logits, aux = transformer.forward(p, cfg, tokens)
        return transformer.lm_loss(logits, labels, cfg.vocab_size, aux)

    loss, grads = jax.value_and_grad(system)(params)
    want, want_g = jax.value_and_grad(ref.loss)(params, M, tokens, labels)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_shares_sum_to_the_uncut_layer():
    """Four chips holding 4 experts each: their routed parts, with the
    shared experts counted once, add up to the uncut reference layer."""
    uncut = dict(M, experts_held=16)
    cfg, params, x = _setup(uncut)
    p = _layer(params)["moe"]
    hn = rms_norm(x, p["norm"], cfg.norm_eps)
    shared = ffn(p["shared"], cfg, hn)
    total = shared
    for off in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=off)
        mine = dict(p, **{k: p[k][off:off + 4]
                          for k in ("w_up", "w_gate", "w_down")})
        out, _, _ = moe.moe_apply(mine, share, x)
        total = total + (out - x - shared)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(ref.moe(p, uncut, hn)), **TOL)


def test_no_token_dropped_under_total_imbalance():
    """Every token's first choice is expert 5 and the rest tie at the
    lowest ids: four experts get every token, where a capacity of
    1.25 * t * k / n_experts would drop most of them."""
    m = dict(M, experts_held=16)
    cfg, params, x = _setup(m)
    p = dict(_layer(params)["moe"])
    p["router"] = jnp.zeros_like(p["router"]).at[:, 5].set(1.0)
    x = jnp.abs(x) + 1.0             # every token scores expert 5 highest
    out, _, rows = moe.moe_apply(p, cfg, x)
    hn = rms_norm(x, p["norm"], cfg.norm_eps)
    _, ids = ref.route(m, hn, p["router"])
    assert bool(jnp.all(ids[..., 0] == 5))
    assert int(rows) == B * S * cfg.top_k
    np.testing.assert_allclose(np.asarray(out - x),
                               np.asarray(ref.moe(p, m, hn)), **TOL)


def test_paged_serving_refuses_latent_attention():
    from repro import configs
    from repro.serving import ServingEngine

    cfg = configs.get_smoke_config("moonlight-16b-a3b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(NotImplementedError, match="mla"):
        ServingEngine(cfg, params)


def _one_node(cfg):
    """A one-node SDM-DSGD run of ``cfg`` with sigma > 0: (config, mesh)."""
    from jax.sharding import AxisType

    from repro.core.sdm_dsgd import SDMConfig
    from repro.train import steps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:1])
    tc = steps.DistributedTrainConfig(
        model=cfg, sdm=SDMConfig(p=1.0, theta=0.5, gamma=0.01, sigma=0.5,
                                 clip_c=1.0, mode="bernoulli"),
        param_dtype=jnp.float32)
    return tc, mesh


def test_router_bias_stays_out_of_the_sdm_state():
    """The score-correction bias is a constant of the program: no leaf of
    the SDM state, and after a step with sigma > 0 that moves every
    parameter it is still the zero the routing reads."""
    from repro.train import steps

    cfg = ModelConfig(**M)
    tc, mesh = _one_node(cfg)
    state = steps.init_distributed_state(tc, mesh, jax.random.PRNGKey(0))
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(state.x)[0]]
    assert not [p for p in paths if "bias" in p]
    assert jax.tree.structure(jax.tree.map(lambda a: a[0], state.x)) == \
        jax.tree.structure(transformer.param_shapes(cfg),
                           is_leaf=lambda v: isinstance(v, tuple))
    before = jax.tree.map(np.asarray, state.x)
    tokens = jnp.zeros((1, S), jnp.int32)
    step = jax.jit(steps.make_distributed_train(tc, mesh))
    for _ in range(2):               # the second step applies the first's d
        state, loss, rows = step(state, tokens, tokens)
    moved = [not np.array_equal(a, np.asarray(b)) for a, b in
             zip(jax.tree.leaves(before), jax.tree.leaves(state.x))]
    assert all(moved) and int(rows) > 0
    np.testing.assert_array_equal(np.asarray(moe.score_correction_bias(cfg)),
                                  np.zeros(M["n_experts"]))


def test_row_count_is_a_declared_release():
    """The step's third output is data-derived: the taint pass sees it
    declared, beside the loss, and finds nothing else leaving the node."""
    from repro.analysis import jaxpr_taint
    from repro.train import steps

    tc, mesh = _one_node(ModelConfig(**M))
    state = steps.state_shape_dtype(tc, mesh)
    tokens = jax.ShapeDtypeStruct((1, S), jnp.int32)
    jaxpr = jax.make_jaxpr(steps.make_distributed_train(tc, mesh))(
        state, tokens, tokens)
    n = len(jax.tree.leaves(state))
    rep = jaxpr_taint.analyze_taint(jaxpr, {n: "data", n + 1: "data"})
    assert rep["findings"] == []
    assert sorted(r["label"] for r in rep["releases"]) == ["loss", "moe_rows"]


def test_reference_matches_transformers_deepseek_v3():
    """Random weights of transformers' own DeepseekV3ForCausalLM (which
    interleaves the rope pairs), copied into the reference's tree with
    the rope columns permuted to the two-halves layout: same logits."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    m = dict(M, experts_held=16, vocab_size=64)
    h, nope, rd, r = 4, 16, 8, 32
    hf_cfg = tf.DeepseekV3Config(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=h,
        num_key_value_heads=h, n_shared_experts=2, n_routed_experts=16,
        num_experts_per_tok=4, n_group=1, topk_group=1,
        routed_scaling_factor=2.446, norm_topk_prob=True, kv_lora_rank=r,
        q_lora_rank=None, qk_nope_head_dim=nope, qk_rope_head_dim=rd,
        v_head_dim=16, first_k_dense_replace=1, rms_norm_eps=1e-5,
        rope_theta=50000.0, rope_interleave=True, tie_word_embeddings=False,
        max_position_embeddings=64, attention_bias=False)
    hf_cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    hf = tf.DeepseekV3ForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for prm in hf.parameters():      # the release's init is near zero
            prm.normal_(0.0, 0.3)
    w = {k: v.detach().numpy().astype(np.float32)
         for k, v in hf.state_dict().items()}
    perm = np.concatenate([np.arange(0, rd, 2), np.arange(1, rd, 2)])

    def q_cols(a):                      # (h * (nope + rd), d) rows per head
        a = a.reshape(h, nope + rd, -1)
        return np.concatenate([a[:, :nope], a[:, nope:][:, perm]],
                              axis=1).reshape(h * (nope + rd), -1).T

    def kv_a_cols(a):                   # (r + rd, d)
        return np.concatenate([a[:r], a[r:][perm]]).T

    def layer(i):
        pre = f"model.layers.{i}."
        att = {"wq": q_cols(w[pre + "self_attn.q_proj.weight"]),
               "wkv_a": kv_a_cols(w[pre + "self_attn.kv_a_proj_with_mqa.weight"]),
               "kv_norm": w[pre + "self_attn.kv_a_layernorm.weight"],
               "wkv_b": w[pre + "self_attn.kv_b_proj.weight"].T,
               "wo": w[pre + "self_attn.o_proj.weight"].T,
               "norm": w[pre + "input_layernorm.weight"]}
        post = w[pre + "post_attention_layernorm.weight"]
        swiglu = lambda q: {"w_gate": w[q + "gate_proj.weight"].T,
                            "w_up": w[q + "up_proj.weight"].T,
                            "w_down": w[q + "down_proj.weight"].T}
        if i == 0:
            return {"attn": att, "mlp": dict(swiglu(pre + "mlp."), norm=post)}
        experts = [swiglu(f"{pre}mlp.experts.{j}.") for j in range(16)]
        moe_p = {k: np.stack([e[k] for e in experts]) for k in experts[0]}
        moe_p.update(router=w[pre + "mlp.gate.weight"].T, norm=post,
                     shared=swiglu(pre + "mlp.shared_experts."))
        return {"attn": att, "moe": moe_p}

    blocks = [layer(1), layer(2)]
    params = jax.tree.map(jnp.asarray, {
        "embed": w["model.embed_tokens.weight"],
        "final_norm": w["model.norm.weight"],
        "lm_head": w["lm_head.weight"].T,
        "prefix": {"0": layer(0)},
        "blocks": {"0": jax.tree.map(lambda *a: np.stack(a), *blocks)}})
    tokens = np.random.default_rng(0).integers(0, 64, size=(2, 24))
    with torch.no_grad():
        want = hf(torch.as_tensor(tokens)).logits.numpy()
    got = ref.logits(params, m, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
