#!/usr/bin/env python3
"""Readings that set a training cell's correctness limits, on the chip at
the cell's own size; the benchmark's runs never run this.

  python3 bench/control.py --workload <cell> --seeds 1 2 3 --control-seeds 1 2 \
      [--faults half no_exchange]

Per seed of ``--seeds``, the program's own readings (the cell's object
built and driven through the check's first steps, as a run does) against
the plain reference (float32, ``highest``). Per seed of
``--control-seeds``, the reference against itself with (a) every tensor
the system keeps in bfloat16 rounded to scaled float8 (the control),
(b) each step's loss and gradient over half the tokens, (c) no exchange
between nodes (cells of more than one node); ``--faults`` picks among
(b) and (c), both by default. A state left unchanged
reads about 1 and needs no run. One reference run serves every reading
of a seed.

Prints one JSON line per seed and reading, and per seed one line with
the reference's clipped-gradient leaf norms.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


FAULTS = {"half": {"fault": "half"}, "no_exchange": {"fault": "no_exchange"}}


def readings(w, config, seed, devices, *, program: bool, controls: bool,
             faults=tuple(FAULTS)):
    from bench.drivers import train

    others = {}
    if program:
        prog, others["program"] = train.program_readings(w, config, seed,
                                                         devices)
        del prog
    if controls:
        runs = {"control": {"control": True}}
        runs.update((f, FAULTS[f]) for f in faults
                    if f != "no_exchange" or len(devices) > 1)
        for name, kw in runs.items():
            others[name] = train.reference_readings(w, config, seed, devices,
                                                    keep_ghat=True, **kw)
    ref = train.reference_readings(
        w, config, seed, devices,
        probes=[o.pop("ghat_flat") for o in others.values()])
    yield "reference", {"clipped_norms": ref["clipped_norms"].tolist()}
    for (name, other), dots in zip(others.items(), ref["probe_dots"]):
        other["grad_dots"] = dots
        yield name, train.compare(other, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", choices=tuple(FAULTS),
                    default=list(FAULTS))
    args = ap.parse_args(argv)

    from bench import harness

    w = harness.load_workload(args.workload)
    config = harness.load_config(w["config"])
    devices = harness.devices_for(w["chips"])
    harness.use_compile_cache()
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        for name, numbers in readings(
                w, config, seed, devices, program=seed in args.seeds,
                controls=seed in args.control_seeds, faults=args.faults):
            print(json.dumps({"seed": seed, "reading": name, **numbers}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
