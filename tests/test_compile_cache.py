"""``repro.launch.compile_cache.compile_counts``: a program compiled with
an empty persistent cache counts a miss and backend compile time; the
same program compiled again, once the in-memory caches are cleared,
counts a hit and retrieval time. In a child process, so the cache
directory set here stays out of the test process."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

CHILD = """
import json
import jax, jax.numpy as jnp
from repro.launch.compile_cache import compile_counts

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
counts = compile_counts()
f = lambda x: jnp.sin(x) @ x.T
x = jnp.ones((64, 64))
readings = []
for _ in range(2):
    before = dict(counts)
    jax.jit(f).lower(x).compile()
    readings.append({k: v - before[k] for k, v in counts.items()})
    jax.clear_caches()
print(json.dumps(readings))
"""


def test_a_miss_then_a_hit(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    first, second = json.loads(p.stdout.strip().splitlines()[-1])
    assert first["cache_misses"] == 1 and first["cache_hits"] == 0
    assert first["backend_compile_s"] > 0
    assert second["cache_hits"] == 1 and second["cache_misses"] == 0
    assert second["cache_retrieval_s"] > 0
    assert any(tmp_path.iterdir())
