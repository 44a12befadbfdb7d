"""chip_smoke.py's phases at smoke_config() size on the CPU (kernels in
interpret mode), imported as functions; and its refusal to report
without a TPU."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro import configs

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
SRC = str(_PATH.parent / "src")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return configs.get_smoke_config("phi3-medium-14b")


def test_cut_keeps_published_widths(smoke):
    pub, cut = smoke.cut_config()
    assert (cut.n_layers, cut.vocab_size) == (1, pub.vocab_size // 8)
    assert cut.padded_vocab == cut.vocab_size == 12544
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "tie_embeddings", "period"):
        assert getattr(cut, f) == getattr(pub, f), f


def test_train_phase(smoke, cfg):
    run = smoke.phase_train(cfg, nodes=1, seq_len=32, steps=2,
                            on_chip=False)
    assert len(run.losses) == 2 and len(run.step_s) == 2
    smoke.check_placement(run)


def test_parity_phase(smoke, cfg):
    smoke.phase_parity(cfg, nodes=1, seq_len=16)


def test_parity_phase_four_nodes():
    """The comparison ``--chips 4`` makes, with a real exchange: a 4-node
    ring (two gossip rounds a step) on 4 faked CPU devices, in a
    subprocess because the device count is fixed when JAX starts."""
    script = (
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('cs', {str(_PATH)!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "from repro import configs\n"
        "m.phase_parity(configs.get_smoke_config('phi3-medium-14b'), "
        "nodes=4, seq_len=16)\n")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "nodes=4" in out.stdout and "gossip_rounds=2" in out.stdout
    # the packed fixed-k transport: one plane, the sender's list and
    # one list per sender compacted
    assert "own_sdm=mask-select compact_draws=3" in out.stdout
    assert "[parity] 4 nodes" in out.stdout, out.stdout


def test_kernels_phase(smoke, cfg, monkeypatch):
    # several reference blocks over the smoke plane, and a ragged tail
    monkeypatch.setattr(smoke, "REF_ROWS", 96)
    smoke.phase_kernels(cfg, batch=3, max_seq=40, page_size=8,
                        dtype=jnp.bfloat16)


def test_serve_phase(smoke, cfg):
    smoke.phase_serve(cfg, n_requests=3, prompt_lens=(5, 20), new_tokens=4,
                      page_size=4, dtype=jnp.float32, on_chip=False)


def test_check_raises_on_failure(smoke):
    with pytest.raises(smoke.SmokeFailure):
        smoke.check(False, "what failed")


def test_compile_cache_placement(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(_PATH.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_main_refuses_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    for line in capsys.readouterr().out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
