"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

Kernels execute in interpret mode (CPU container; TPU is the target).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attn.ops import flash_attention
from repro.kernels.sdm_update import ref as sdm_ref
from repro.kernels.sdm_update.ops import sdm_update
from repro.kernels.sdm_update.sdm_update import LANE, sdm_update_pallas


# --------------------------------------------------------------------------
# sdm_update
# --------------------------------------------------------------------------

def _operands(rows, seed=0):
    rng = np.random.default_rng(seed)
    shape = (rows, LANE)
    f = lambda: jnp.asarray(rng.normal(size=shape), jnp.float32)
    bits = lambda: jnp.asarray(
        rng.integers(0, 2**32, size=shape, dtype=np.uint32))
    return f(), f(), f(), f(), bits(), bits(), bits()


SDM_KW = dict(p=0.25, theta=0.4, gamma=0.05, sigma=0.7, clip_c=1.5,
              self_w=1.0 / 3.0)


@pytest.mark.parametrize("rows,block_rows", [(8, 8), (16, 8), (64, 32)])
def test_sdm_update_matches_ref(rows, block_rows):
    ops = _operands(rows)
    out_k = sdm_update_pallas(*ops, block_rows=block_rows, interpret=True,
                              **SDM_KW)
    out_r = sdm_ref.sdm_update_ref(*ops, **SDM_KW)
    for a, b, name in zip(out_k, out_r, ("x_new", "s_new", "sd")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6, err_msg=name)


@pytest.mark.parametrize("kw", [
    dict(SDM_KW, sigma=0.0),            # no noise branch
    dict(SDM_KW, clip_c=None),          # no clip branch
    dict(SDM_KW, p=1.0),                # no sparsification
    dict(SDM_KW, theta=1.0),            # DC-DSGD corner
])
def test_sdm_update_branch_configs(kw):
    ops = _operands(8, seed=3)
    out_k = sdm_update_pallas(*ops, block_rows=8, interpret=True, **kw)
    out_r = sdm_ref.sdm_update_ref(*ops, **kw)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_sdm_update_semantics():
    """Kernel implements Algorithm 1's algebra: check against hand-computed
    dense formulas (not just the ref module)."""
    ops = _operands(8, seed=5)
    x, s, nb, g, mb, n1, n2 = ops
    kw = dict(SDM_KW, sigma=0.0, clip_c=None, p=1.0)
    x2, s2, sd = sdm_update_pallas(*ops, block_rows=8, interpret=True, **kw)
    s_new = s + nb
    y = (1 - kw["theta"]) * x + kw["theta"] * (
        kw["self_w"] * x + s_new - kw["gamma"] * g)
    np.testing.assert_allclose(np.asarray(sd), np.asarray(y - x), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(x2), np.asarray(y), rtol=1e-5,
                               atol=1e-6)


def test_sdm_update_pytree_wrapper():
    tree = {"a": jnp.ones((3, 5)), "b": jnp.arange(7.0)}
    zeros = jax.tree.map(jnp.zeros_like, tree)
    key = jax.random.PRNGKey(0)
    x2, s2, sd = sdm_update(tree, zeros, zeros, tree, key, use_kernel=True,
                            block_rows=8, **SDM_KW)
    xr, sr, sdr = sdm_update(tree, zeros, zeros, tree, key, use_kernel=False,
                             **SDM_KW)
    for t1, t2 in ((x2, xr), (s2, sr), (sd, sdr)):
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6), t1, t2)


@given(seed=st.integers(0, 2**31 - 1),
       p=st.sampled_from([0.1, 0.5, 1.0]),
       theta=st.floats(0.05, 1.0),
       sigma=st.sampled_from([0.0, 0.5]))
@settings(max_examples=25, deadline=None)
def test_sdm_update_property_sweep(seed, p, theta, sigma):
    ops = _operands(8, seed=seed % 1000)
    kw = dict(p=p, theta=theta, gamma=0.01, sigma=sigma, clip_c=2.0,
              self_w=0.5)
    out_k = sdm_update_pallas(*ops, block_rows=8, interpret=True, **kw)
    out_r = sdm_ref.sdm_update_ref(*ops, **kw)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

def _qkv(b, sq, skv, h, kvh, dh, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, sq, h, dh)), dtype) * 0.5
    k = jnp.asarray(rng.normal(size=(b, skv, kvh, dh)), dtype) * 0.5
    v = jnp.asarray(rng.normal(size=(b, skv, kvh, dh)), dtype) * 0.5
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sq,skv,dh", [(128, 128, 64), (256, 384, 128),
                                       (128, 160, 32)])
def test_flash_matches_ref_shapes_dtypes(sq, skv, dh, dtype):
    q, k, v = _qkv(2, sq, skv, 4, 4, dh, dtype)
    out = flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                          use_kernel=True, interpret=True)
    ref = flash_attention(q, k, v, causal=False, use_kernel=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None),
    (True, 64, None),        # gemma2 sliding window
    (True, None, 50.0),      # gemma2 attn softcap
    (True, 64, 50.0),
])
def test_flash_masking_variants(causal, window, softcap):
    q, k, v = _qkv(1, 256, 256, 2, 2, 64, jnp.float32, seed=7)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, use_kernel=True, interpret=True)
    ref = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_gqa_groups():
    q, k, v = _qkv(2, 128, 128, 8, 2, 64, jnp.float32, seed=9)
    out = flash_attention(q, k, v, causal=True, use_kernel=True,
                          interpret=True)
    ref = flash_attention(q, k, v, causal=True, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_flash_matches_model_sdpa():
    """Cross-validate the kernel against the model's _sdpa (independent)."""
    from repro.models.layers import _sdpa
    b, s, h, dh = 2, 128, 4, 64
    q, k, v = _qkv(b, s, s, h, h, dh, jnp.float32, seed=11)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    ref = _sdpa(q, k, v, q_positions=pos, kv_positions=pos, causal=True,
                window=None, softcap_val=None)
    out = flash_attention(q, k, v, causal=True, use_kernel=True,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)


@given(seed=st.integers(0, 10_000),
       sq=st.sampled_from([128, 256]),
       skv=st.sampled_from([128, 192, 320]),
       causal=st.booleans())
@settings(max_examples=15, deadline=None)
def test_flash_property_sweep(seed, sq, skv, causal):
    q, k, v = _qkv(1, sq, skv, 2, 1, 64, jnp.float32, seed=seed)
    if causal and sq > skv:
        skv = sq  # causal requires kv covers q positions in this harness
        q, k, v = _qkv(1, sq, skv, 2, 1, 64, jnp.float32, seed=seed)
    out = flash_attention(q, k, v, causal=causal, use_kernel=True,
                          interpret=True)
    ref = flash_attention(q, k, v, causal=causal, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)


# --------------------------------------------------------------------------
# wire_compress: fused quantize+pack vs oracles
# --------------------------------------------------------------------------

from repro.core.compressor import FusedQSGDCompressor, QSGDCompressor  # noqa: E402
from repro.kernels import wire_compress  # noqa: E402


def _plane(rows, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, LANE)), jnp.float32)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("rows", [8, 16, 64])
def test_qsgd_pack_kernel_bitequal_ref(bits, rows):
    """Pallas kernel byte image == pure-jnp oracle, bit for bit."""
    xf = _plane(rows, seed=bits)
    u = jax.random.uniform(jax.random.PRNGKey(rows + bits), xf.shape)
    norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
    out_k = wire_compress.qsgd_pack(xf, u, norm, bits=bits, use_kernel=True)
    out_r = wire_compress.qsgd_pack(xf, u, norm, bits=bits, use_kernel=False)
    assert out_k.dtype == jnp.uint8 and out_k.shape == out_r.shape
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_qsgd_pack_kernel_bitequal_unfused_compressor(bits):
    """Fused byte image == the unfused QSGDCompressor pack, same key."""
    xf = _plane(8, seed=17)
    key = jax.random.PRNGKey(5)
    comp = QSGDCompressor(p=1.0, bits=bits)
    pay = comp.compress(key, xf)
    u = jax.random.uniform(key, xf.shape)
    norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
    fused = wire_compress.qsgd_pack(xf, u, norm, bits=bits)
    if bits == 8:
        # unfused b=8 ships signed int8 q; fused ships offset (q + s) u8
        unfused = (np.asarray(pay.values).astype(np.int32)
                   .reshape(-1) + comp.levels).astype(np.uint8)
    else:
        unfused = np.asarray(pay.values).reshape(-1)
    np.testing.assert_array_equal(np.asarray(fused), unfused)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(71,), (3, 5, 11), (9, 33)])
def test_qsgd_pack_ref_path_odd_shapes(bits, shape):
    """Non-plane shapes route to the oracle and still decode exactly."""
    rng = np.random.default_rng(1)
    xf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(2), shape)
    norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
    data = wire_compress.qsgd_pack(xf, u, norm, bits=bits)
    tail = jax.lax.bitcast_convert_type(norm, jnp.uint8)
    buf = jnp.concatenate([data, tail])
    dec = wire_compress.qsgd_decode_ref(buf, shape, bits=bits)
    comp = QSGDCompressor(p=1.0, bits=bits)
    pay = comp.compress(jax.random.PRNGKey(2), xf)
    np.testing.assert_array_equal(np.asarray(dec),
                                  np.asarray(comp.decompress(pay)))


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_fused_compressor_roundtrip_bitequal_qsgd(bits):
    """FusedQSGDCompressor decompress(compress(x)) == qsgd's, bitwise,
    and matches the qsgd_decode_ref oracle on the same buffer."""
    xf = _plane(16, seed=23)
    key = jax.random.PRNGKey(9)
    fused = FusedQSGDCompressor(p=1.0, bits=bits)
    plain = QSGDCompressor(p=1.0, bits=bits)
    fp = fused.compress(key, xf)
    assert fp.scale is None and fp.values.dtype == jnp.uint8
    out_f = fused.decompress(fp)
    out_p = plain.decompress(plain.compress(key, xf))
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_p))
    out_o = wire_compress.qsgd_decode_ref(fp.values, fp.shape, bits=bits)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_o))


def test_fused_compressor_wire_bits_inherited():
    for bits in (2, 4, 8):
        f = FusedQSGDCompressor(p=1.0, bits=bits)
        q = QSGDCompressor(p=1.0, bits=bits)
        for shape in ((8, 128), (71,), (3, 5, 11)):
            assert f.wire_bits(shape) == q.wire_bits(shape)
            # single-buffer format: the payload byte count IS the charge
            d = int(np.prod(shape))
            k = wire_compress.pack_factor(bits)
            assert f.wire_bits(shape) == (-(-d // k)) * 8 + 32


def test_fused_compressor_rejects_odd_bits():
    with pytest.raises(ValueError):
        FusedQSGDCompressor(p=1.0, bits=3)


@given(seed=st.integers(0, 10_000), bits=st.sampled_from([2, 4, 8]),
       rows=st.sampled_from([8, 24, 40]))
@settings(max_examples=15, deadline=None)
def test_qsgd_pack_property_sweep(seed, bits, rows):
    rng = np.random.default_rng(seed)
    xf = jnp.asarray(rng.normal(size=(rows, LANE)), jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(seed), xf.shape)
    norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
    out_k = wire_compress.qsgd_pack(xf, u, norm, bits=bits, use_kernel=True)
    out_r = wire_compress.qsgd_pack(xf, u, norm, bits=bits, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
