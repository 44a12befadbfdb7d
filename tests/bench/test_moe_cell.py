"""``moonlight-sdm-1node-randk-8k``: a sound tiny run of the expert model
through the system's step is correct under the cell's limits, each fault
planted in the timed path makes ``correct`` come out false, and the
step's spans carry the new sublayers (see moe_run.py)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
CELL = "moonlight-sdm-1node-randk-8k"


def run(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "moe_run.py"), CELL, fault],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    out = run("none")
    assert out["correct"] is True, out["check"]
    gap = out["check"]["loss_gap"]
    assert gap["value"] < 1e-3 * gap["limit"], out["check"]


@pytest.mark.parametrize("fault", ["half", "zero_leaf"])
def test_fault_is_caught(fault):
    out = run(fault)
    assert out["correct"] is False, out["check"]
