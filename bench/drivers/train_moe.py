"""Training cells of models with expert layers: the ``train`` driver's
cell, run, check and readings (see its module doc), where the system's
step also returns the rows its router sent to the experts this chip
holds, summed over the MoE layers.

The window's rows go to ``counters["moe_rows"]`` (read on the host once
the window has closed), the model FLOPs per token are those of
latent attention and the held share of the routed experts
(``bench.mla_moe_flops``), and ``counters["gmm_flops_per_row"]`` is what
a row costs the experts' grouped matmuls, forward and backward.
"""
from __future__ import annotations

import time

import numpy as np

from bench import mla_moe_flops
from bench.drivers import train


class Program(train.Program):
    """The system's compiled step, whose third output is the step's row
    count; ``routed`` keeps one count a step, on the device until read."""

    def __init__(self, w: dict, config: dict, seed: int, devices):
        self.routed = []
        super().__init__(w, config, seed, devices)

    def step(self, args) -> float:
        import jax

        self.state, loss, rows = self.compiled(self.state, *args)
        jax.block_until_ready((self.state, loss))
        self.t += 1
        self.routed.append(rows)
        return float(loss)


def program_readings(w: dict, config: dict, seed: int, devices):
    """As ``train.program_readings``, with this driver's ``Program``."""
    prog = Program(w, config, seed, devices)
    losses = []
    for t in range(w["check_steps"] + 1):
        losses.append(prog.step(prog.feed(t)))
        if t == 0:
            g_norms, ghat = prog.grad_norms(), prog.first_ghat()
    return prog, {"losses": losses[:w["check_steps"]], "grad_norms": g_norms,
                  "ghat_flat": ghat, "change_norms": prog.change_norms()}


def run(w: dict, config: dict, *, seed: int, seconds: float, window,
        devices, t_start: float) -> dict:
    from bench.harness import memory_peak_bytes

    t_build = time.perf_counter()
    prog, readings = program_readings(w, config, seed, devices)
    t_check = time.perf_counter() - prog.built
    footprint = prog.footprint_bytes()

    steps = failed = 0
    with window as win:
        setup_s = win.start - t_start
        split = {"to_build": t_build - t_start,
                 "build": prog.built - t_build, "check": t_check,
                 "rest": win.start - prog.built - t_check}
        while True:
            with win.annotate("bench.feed"):
                args = prog.feed(prog.t)
            with win.annotate("bench.step"):
                loss = prog.step(args)
            steps += 1
            failed += not np.isfinite(loss)
            if time.perf_counter() - win.start >= seconds:
                break
    tokens = steps * prog.n * prog.rows * prog.seq
    routed = sum(int(r) for r in prog.routed[-steps:])
    peak = memory_peak_bytes(devices)
    del prog
    ref = train.reference_readings(w, config, seed, devices,
                                   probes=[readings.pop("ghat_flat")])
    readings["grad_dots"] = ref["probe_dots"][0]
    m = config["model"]
    return {
        "kind": "train", "attempted": steps, "failed": failed,
        "setup_s": setup_s, "window_s": win.seconds,
        "end_to_end": {"train_tokens_per_s": (tokens / win.seconds,
                                              "tokens/s")},
        "numbers": train.compare(readings, ref),
        "memory_peak_bytes": max(peak, footprint),
        "counters": {"steps": steps, "tokens": tokens, "nodes": len(devices),
                     "chips": len(devices),
                     "flops_per_token": mla_moe_flops.train_flops_per_token(
                         m, w["job"]["seq_len"]),
                     "moe_rows": routed,
                     "gmm_flops_per_row": mla_moe_flops.gmm_flops_per_row(m),
                     "footprint_bytes": footprint,
                     "allocator_peak_bytes": peak,
                     "setup_split_s": split},
    }
