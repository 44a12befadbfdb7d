"""The main-path Pallas kernels compile for a TPU v5e at real shapes.

Compiled with ``interpret=False`` for a described (not attached) v5e
chip: what Mosaic refuses here — an illegal block shape, an unsupported
layout cast — would fail the chip run. Shapes are phi3-medium-14b's: a
slice of its wire plane, and its KV heads (10 x 128, group 4) in 16-token
pages. The fixed-k sender pack compiles there too, as XLA's gather with
no kernel. All compiles stay in this one file and in fixtures, because
only one process may load the TPU compiler at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gossip
from repro.kernels.flash_attn.decode import paged_flash_decode_pallas
from repro.kernels.wire_compress import qsgd_pack_pallas

PLANE_ROWS = 65_537          # odd: exercises the sub-byte row padding
KV_HEADS, GROUP, HEAD_DIM, PAGE = 10, 4, 128, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_qsgd_pack_compiles_for_v5e(one_chip, bits):
    plane = jax.ShapeDtypeStruct((PLANE_ROWS, 128), jnp.float32,
                                 sharding=one_chip)
    inv = jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x, u, i: qsgd_pack_pallas(x, u, i, bits=bits,
                                         interpret=False),
        plane, plane, inv)
    assert "tpu_custom_call" in text


# 13,108 kept rows (p = 0.2 of the plane) and 565, not a multiple of 8
@pytest.mark.parametrize("kept", [13_108, 565])
def test_fixedk_pack_compiles_for_v5e_without_kernel(one_chip, kept,
                                                     monkeypatch):
    """The sender's fixed-k pack of lane-dense rows (block:128) is XLA's
    gather on the chip: no Pallas kernel. The backend reads as a TPU
    here, so a kernel left to ``resolve_interpret`` would compile as one
    instead of being interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda db, keys, keep: gossip._packed_selection(
            db, keys, keep, kept, kept, kept / PLANE_ROWS),
        s((PLANE_ROWS, 128), jnp.float32), s((PLANE_ROWS,), jnp.int32),
        s((PLANE_ROWS,), jnp.bool_))
    assert "tpu_custom_call" not in text
    assert " gather(" in text


def test_paged_flash_decode_compiles_for_v5e(one_chip):
    batch, n_blocks = 8, 34
    n_pages = batch * n_blocks + 1
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pages = s((n_pages, KV_HEADS, PAGE, HEAD_DIM), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v, t, n: paged_flash_decode_pallas(q, k, v, t, n,
                                                        interpret=False),
        s((batch, KV_HEADS, GROUP, HEAD_DIM), jnp.bfloat16), pages, pages,
        s((batch, n_blocks), jnp.int32), s((batch,), jnp.int32))
    assert "tpu_custom_call" in text


def test_moe_grouped_matmul_compiles_for_v5e(one_chip):
    """Moonlight-16B-A3B's expert layer at its widths (8 held of 64
    experts, 1408 wide, top 6) over 8,192 tokens, forward and backward:
    the held experts' rows run through XLA's ragged-dot kernels."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe

    cfg = dataclasses.replace(get_config("moonlight-16b-a3b"), experts_held=8)
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                           sharding=one_chip)
    params = jax.tree.map(lambda spec: s(spec.shape), moe.moe_specs(cfg),
                          is_leaf=lambda v: hasattr(v, "axes"))

    def loss(p, x):
        out, _, rows = moe.moe_apply(p, cfg, x)
        return jnp.sum(out.astype(jnp.float32)), rows

    text = _compiled_text(jax.grad(loss, has_aux=True), params,
                          s((1, 8192, cfg.d_model)))
    assert "ragged-dot" in text
