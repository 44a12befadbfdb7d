"""The control, the plain reference with what the system keeps in
bfloat16 rounded to scaled float8, comes out not correct under each
cell's limits, through the same probe of the first gradient that a run
makes; run here at a tiny size (the chip readings at the cells' own
sizes, which set the limits, are in PERF.md)."""
import os
import subprocess
import sys

import pytest

from _faults import HERE

SCRIPT = r'''
import json, os, sys
sys.path[:0] = [sys.argv[1]]
import fault_run
import jax
from bench import harness
from bench.drivers import train
w = fault_run.tiny_cell(harness.load_workload(sys.argv[2]))
cfg = dict(fault_run.CONFIG, dtype="bfloat16")
devs = jax.devices()[:w["chips"]]
low = train.reference_readings(w, cfg, 5, devs, control=True, keep_ghat=True)
ref = train.reference_readings(w, cfg, 5, devs, probes=[low.pop("ghat_flat")])
low["grad_dots"] = ref["probe_dots"][0]
numbers = train.compare(low, ref)
print(json.dumps([harness.judge(numbers, w["limits"])[0], numbers]))
'''


@pytest.mark.parametrize("cell", ["phi3-sdm-ring4-block128",
                                  "chatglm3-sdm-1node-randk"])
def test_control_is_not_correct(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(HERE), cell],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    correct, numbers = __import__("json").loads(p.stdout.splitlines()[-1])
    assert correct is False, numbers
