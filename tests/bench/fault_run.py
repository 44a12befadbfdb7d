"""Drive one run of a benchmark cell on the CPU at a tiny size, past the
harness's look for a chip, with one fault planted in the timed path;
prints the run's result line last.

  python tests/bench/fault_run.py <cell> <fault>

The tiny run keeps the cell's job (method, compressor, nodes) and its
limits, on a tiny model of the configurations' family
(GQA, half rotary, qkv bias, untied head) at float32, so a sound run
agrees with the reference to rounding, far under the limits.

Faults: none | unchanged (the step returns its state as it got it) |
half (the loss and its gradient over half of each batch's tokens) |
no_exchange (every payload between nodes arrives as zeros) | zero_leaf
(the backward pass gives zero for the leaf whose gradient is largest).
"""
from __future__ import annotations

import os
import pathlib
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 128,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
        "vocab_size": 500, "tie_embeddings": False, "qkv_bias": True,
        "rope_fraction": 0.5, "norm_eps": 1e-5, "vocab_pad_multiple": 16}
CONFIG = {"name": "tiny", "reference": "dense_reference", "dtype": "float32",
          "model": TINY}


def tiny_cell(w: dict) -> dict:
    """The cell ``w`` at a tiny size, with its limits."""
    w = dict(w, name="tiny-" + w["name"], config="tiny")
    w["job"] = dict(w["job"], seq_len=64)
    return w


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import clipping, gossip
    from repro.models import transformer
    from repro.train import steps

    if fault == "unchanged":
        make = steps.make_distributed_train

        def frozen(tc, mesh, base_key=None):
            step = make(tc, mesh, base_key)

            def same(state, *a):
                _, loss = step(state, *a)
                return state, loss
            return same
        steps.make_distributed_train = frozen
    elif fault == "half":
        lm_loss = transformer.lm_loss

        def half(logits, labels, *a, **k):
            s = labels.shape[-1] // 2
            return lm_loss(logits[..., :s, :], labels[..., :s], *a, **k)
        transformer.lm_loss = half
    elif fault == "no_exchange":
        gossip._wire_ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)
    elif fault == "zero_leaf":
        clip_tree = clipping.clip_tree

        def zeroed(grads, c):
            leaves, treedef = jax.tree.flatten(clip_tree(grads, c))
            norms = jnp.stack([jnp.sum(jnp.square(a.astype(jnp.float32)))
                               for a in leaves])
            top = jnp.argmax(norms)
            return jax.tree.unflatten(treedef, [
                jnp.where(top == i, jnp.zeros_like(a), a)
                for i, a in enumerate(leaves)])
        clipping.clip_tree = zeroed
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    import jax

    from bench import harness
    from bench.run import run_cell

    cell, fault = sys.argv[1], sys.argv[2]
    w = tiny_cell(harness.load_workload(cell))
    plant(fault)
    with tempfile.TemporaryDirectory() as cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
        return run_cell(w, CONFIG, seed=2 ** 33 + 5, seconds=0.5,
                        trace=False, devices=jax.devices()[:w["chips"]],
                        t_start=time.perf_counter())


if __name__ == "__main__":
    sys.exit(main())
