"""Benchmark aggregator: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per the repo convention.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig2,theory
"""
from __future__ import annotations

import argparse
import sys
import traceback


BENCHES = {
    "theory": ("benchmarks.theory_tradeoff",
               "Thm 4 m^4 scaling, Prop 5 1/p^2 gap, Lemma 1 terms"),
    "fig2": ("benchmarks.fig2_divergence",
             "Fig 2: DC-DSGD divergence at p=0.2 vs SDM-DSGD"),
    "fig3": ("benchmarks.fig3_comm_efficiency",
             "Fig 3: loss/accuracy vs communicated non-zero elements"),
    "table1": ("benchmarks.table1_privacy_accuracy",
               "Table 1: accuracy under (eps, delta)-DP budgets"),
    "perf": ("benchmarks.perf_wire",
             "wire-plane perf snapshot -> BENCH_perf.json (permutes/step, "
             "wire bits, sorts, fusion factor)"),
    "sim": ("benchmarks.sim_edge",
            "edge-fleet simulation -> BENCH_sim.json (simulated seconds-"
            "to-target, wire bits, epsilon per method x fault scenario)"),
    "serve": ("benchmarks.serve_bench",
              "serving snapshot -> BENCH_serve.json (continuous vs static "
              "tok/s, per-token latency, TTFT, paged-KV footprint, decode "
              "launches)"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    args = ap.parse_args()
    names = list(BENCHES) if args.only is None else args.only.split(",")

    failures = []
    for name in names:
        module_name, desc = BENCHES[name]
        print(f"# === {name}: {desc}", flush=True)
        try:
            mod = __import__(module_name, fromlist=["run"])
            mod.run()
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"# FAILED: {failures}")
        sys.exit(1)
    print("# all benchmarks passed")


if __name__ == "__main__":
    main()
