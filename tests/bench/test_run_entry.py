"""``bench/run.py`` refuses to run without a TPU: it exits non-zero and
prints no result, also from a directory holding only the benchmark."""
import os
import shutil
import subprocess
import sys

from bench import harness


def run(root, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload",
         "chatglm3-sdm-1node-randk", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, cwd=cwd,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = run(harness.ROOT, harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not tpu" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    p = run(tmp_path, tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
