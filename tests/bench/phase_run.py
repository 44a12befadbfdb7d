"""Build a benchmark cell's step on the CPU at a tiny size (as
fault_run.py does), run it once under the profiler, and print, as one
JSON object, what ``bench.phases`` finds in the trace's copy of the
step's optimized HLO.

  python tests/bench/phase_run.py <cell>

``phases``: {phase: instructions}; ``backward``: {phase: instructions}
over the instructions whose ``op_name`` has a transpose (the backward
pass); ``programs``: the programs the trace holds.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import tempfile
from collections import Counter

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests" / "bench")]


def main() -> int:
    import jax

    from bench import harness, phases
    from bench.drivers.train import Program
    from fault_run import CONFIG, tiny_cell

    w = tiny_cell(harness.load_workload(sys.argv[1]))
    prog = Program(w, CONFIG, seed=2 ** 33 + 7,
                   devices=jax.devices()[:w["chips"]])
    args = prog.feed(0)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        prog.step(args)
        jax.profiler.stop_trace()
        found = sorted(pathlib.Path(d).glob("plugins/profile/*/*.xplane.pb"))
        programs = phases.programs(found[-1].read_bytes())
    # the step is the largest program the trace holds
    step = max(programs.values(), key=len)
    print(json.dumps({
        "programs": sorted(programs),
        "phases": Counter(phases.phase(o) for o in step.values()),
        "backward": Counter(phases.phase(o) for o in step.values()
                            if "transpose(" in o),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
