#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``. With ``--trace 0`` the
result reports the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result reports the per-layer
metrics read from the trace and the run's counters. The last line of
standard output is the result as one JSON object; the numbers that
decide ``correct`` are printed beside their limits as the last lines of
standard error and under the result's last key, ``check``.

Exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for. There is no fallback to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    w = harness.load_workload(args.workload)
    config = harness.load_config(w["config"])
    try:
        devices = harness.devices_for(w["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return run_cell(w, config, seed=args.seed % (1 << 64), seconds=args.seconds,
                    trace=bool(args.trace), devices=devices)


def run_cell(w, config, *, seed, seconds, trace, devices,
             t_start=None) -> int:
    """Everything after the chip check: set-up, window, check, result."""
    from bench import harness, trace as trace_mod

    harness.use_compile_cache()
    window = harness.Window(trace, w["name"])
    rec = harness.driver(w["kind"]).run(
        w, config, seed=seed, seconds=seconds, window=window,
        devices=devices, t_start=T_START if t_start is None else t_start)
    correct, check = harness.judge(rec["numbers"], w.get("limits", {}))
    correct &= rec["failed"] == 0
    dev = devices[0]
    rec["device_kind"] = dev.device_kind
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    breakdown = None
    if trace:
        summary = trace_mod.reduce(window.xplane(), len(devices),
                                   window.seconds)
        metrics = {}
        for name, mod in harness.metric_readers().items():
            value = mod.read(rec, summary)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    else:
        metrics = {name: {"value": v, "unit": u}
                   for name, (v, u) in rec["end_to_end"].items()}
        metrics["setup_s"] = {"value": rec["setup_s"], "unit": "s"}
    print(f"counters {rec['counters']}", file=sys.stderr, flush=True)
    harness.print_check(check)
    print(harness.result_line(
        correct=correct, attempted=rec["attempted"], failed=rec["failed"],
        metrics=metrics, device=device, check=check, breakdown=breakdown),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
