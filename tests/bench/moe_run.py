"""Drive one run of the expert-model cell on the CPU at a tiny size (as
fault_run.py drives the dense cells), optionally with one fault planted
in the timed path; prints the run's result line last.

  python tests/bench/moe_run.py <cell> <fault>

The tiny model keeps the configuration's block (a leading dense layer,
latent attention, a sigmoid router over 16 experts of which 8 are held,
top 4, shared experts, an untied head) and its schedule (remat, query
blocks) at float32, so a sound run agrees with the reference to
rounding, far under the cell's limits. Faults: as fault_run.py's
``none``, ``half`` and ``zero_leaf``.
"""
from __future__ import annotations

import os
import pathlib
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests" / "bench")]

TINY = {"name": "tiny-moe", "family": "moe", "n_layers": 3, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 4, "d_ff": 128, "vocab_size": 500,
        "prefix": [{"mixer": "mla", "ffn": "mlp"}],
        "period": [{"mixer": "mla", "ffn": "moe"}],
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_experts": 16, "top_k": 4, "d_ff_expert": 32,
        "experts_held": 8, "expert_offset": 0, "n_shared_experts": 2,
        "router_scoring": "sigmoid", "routed_scaling_factor": 2.446,
        "rope_theta": 50000.0, "norm_eps": 1e-05, "tie_embeddings": False,
        "vocab_pad_multiple": 16, "attn_chunk_q": 16, "remat": True}
CONFIG = {"name": "tiny-moe", "reference": "mla_moe_reference",
          "dtype": "float32", "model": TINY}


def main() -> int:
    import jax

    from bench import harness
    from bench.run import run_cell
    from fault_run import plant, tiny_cell

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
