"""input_specs / skip_reason coverage for every (arch x shape)."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch import shapes as shapes_mod


ALL = sorted(configs.ALIASES)


def test_shape_table_matches_assignment():
    s = shapes_mod.SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)


@pytest.mark.parametrize("arch", ALL)
def test_long500k_eligibility(arch):
    cfg = configs.get_config(arch)
    reason = shapes_mod.skip_reason(cfg, shapes_mod.SHAPES["long_500k"])
    if arch in ("rwkv6-3b", "jamba-v0.1-52b", "gemma2-2b"):
        assert reason is None
    else:
        assert reason is not None  # documented skip


@pytest.mark.parametrize("arch", ALL)
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k"])
def test_input_specs_structure(arch, shape):
    cfg = configs.get_config(arch)
    case = shapes_mod.SHAPES[shape]
    specs = shapes_mod.input_specs(cfg, case)
    if case.kind == "train":
        assert specs["tokens"].shape == (case.global_batch, case.seq_len)
        assert specs["labels"].dtype == jnp.int32
    elif case.kind == "prefill":
        assert specs["tokens"].shape == (case.global_batch, case.seq_len)
        assert "cache" in specs
    else:
        assert specs["token"].shape == (case.global_batch,)
        # cache covers the full context length
        if not cfg.is_attention_free:
            kv = [l for l in jax.tree.leaves(specs["cache"])
                  if hasattr(l, "shape") and len(l.shape) == 5
                  and l.shape[2] > 1000]  # KVCache, not rwkv/mamba states
            assert kv and kv[0].shape[2] == case.seq_len
    # modality stubs present exactly for audio/vlm
    assert ("context" in specs) == (cfg.family in ("audio", "vlm"))
    # every leaf is a ShapeDtypeStruct (no allocation)
    for leaf in jax.tree.leaves(specs):
        assert isinstance(leaf, (jax.ShapeDtypeStruct, jax.Array)) and \
            not isinstance(leaf, jax.Array)


def test_all_40_pairs_enumerated():
    """The assigned 10 archs and moonlight-16b-a3b x 4 shapes = 44; 36
    runnable + 8 documented skips (moonlight's: long_500k, full
    attention)."""
    runnable, skipped = 0, 0
    for arch in ALL:
        cfg = configs.get_config(arch)
        for case in shapes_mod.SHAPES.values():
            if shapes_mod.skip_reason(cfg, case) is None:
                runnable += 1
            else:
                skipped += 1
    assert runnable + skipped == 44
    assert runnable == 36 and skipped == 8
