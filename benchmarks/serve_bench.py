"""Serving engine snapshot -> BENCH_serve.json.

One ragged-arrival workload (fixed seed, high budget variance — the
traffic shape continuous batching exists for) is served by both engines
after a warmup pass, and the continuous engine's jitted paged decode
step is compiled standalone to count kernel launches:

    tok_s               generated tokens / serve() wall-clock
    p50_ms / p95_ms     per-token decode latency percentiles
                        (step wall / tokens emitted that step)
    ttft_p50_ms / ttft_max_ms
                        submit -> first-token-available
    decode_steps        jitted decode steps executed for the workload
                        (continuous retires+admits mid-flight, so it
                        needs fewer than the static drain-the-batch loop)
    pages_peak / pages_dense / page_frac
                        paged-KV footprint vs the dense
                        max_batch x max_seq reservation (continuous only)
    decode_launches_flash / decode_launches_ref
                        ``hlo_analysis.launch_count`` of ONE compiled
                        decode step, flash (interpret-mode pallas paged
                        kernel) vs XLA gather reference path

Wall-clock here is CPU-host relative (static vs continuous under the
same conditions) — the structural numbers (decode_steps, launches,
pages) are the portable signal. ``benchmarks/baselines/serve.json`` pins
what CI regresses against (``python -m benchmarks.check_serve``).

Baseline refresh (intentional structure changes):
``BENCH_SERVE_OUT=benchmarks/baselines/serve.json python -m
benchmarks.serve_bench`` and commit the diff.
"""
from __future__ import annotations

import json
import os
import time

OUT_PATH = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")

ARCH = "phi3-medium-14b"
MAX_BATCH = 3
MAX_SEQ = 64
PAGE_SIZE = 8
N_REQUESTS = 12
BUDGETS = [16, 1, 2, 12, 1, 3, 16, 2, 8, 1, 4, 12]   # high variance
SEED = 7


def _requests(cfg):
    import numpy as np

    from repro.serving import Request
    rng = np.random.default_rng(SEED)
    lens = rng.integers(2, 17, size=N_REQUESTS).tolist()
    return [Request(prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=m)
            for n, m in zip(lens, BUDGETS)]


def _percentile(xs, q):
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(int(q * len(xs)), len(xs) - 1)
    return xs[i]


def _engine_record(case, eng, cfg):
    import numpy as np
    eng.serve(_requests(cfg))               # warmup: compile all shapes
    t0 = time.monotonic()
    out = eng.serve(_requests(cfg))
    wall = time.monotonic() - t0
    stats = eng.last_stats
    per_tok = [w / max(t, 1) * 1e3
               for w, t in zip(stats.step_wall_s, stats.step_tokens)]
    tokens = sum(len(r.output) for r in out)
    rec = {
        "case": case,
        "tokens": tokens,
        "tok_s": round(tokens / wall, 1),
        "p50_ms": round(_percentile(per_tok, 0.50), 3),
        "p95_ms": round(_percentile(per_tok, 0.95), 3),
        "ttft_p50_ms": round(_percentile(stats.ttft_s, 0.50) * 1e3, 3),
        "ttft_max_ms": round(max(stats.ttft_s) * 1e3, 3),
        "decode_steps": stats.decode_steps,
    }
    if stats.pages_dense_equiv:
        rec["pages_peak"] = stats.pages_peak
        rec["pages_dense"] = stats.pages_dense_equiv
        rec["page_frac"] = round(
            stats.pages_peak / stats.pages_dense_equiv, 3)
    assert np.all([len(r.output) > 0 for r in out])
    return rec


def _decode_launches(cfg, params, *, use_flash):
    """launch_count of one compiled paged decode step."""
    import jax
    import jax.numpy as jnp

    from repro.launch import hlo_analysis
    from repro.models import transformer
    from repro.serving import PagedKVCache

    kv = PagedKVCache(cfg, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                      page_size=PAGE_SIZE)

    def step(p, tok, pages, tables, offsets, emit):
        return transformer.decode_step_paged(
            p, cfg, tok, pages, {}, tables, offsets, emit,
            use_flash=use_flash)

    tok = jnp.zeros((MAX_BATCH,), jnp.int32)
    offsets = jnp.ones((MAX_BATCH,), jnp.int32)
    emit = jnp.ones((MAX_BATCH,), bool)
    compiled = jax.jit(step).lower(params, tok, kv.pages, kv.tables(),
                                   offsets, emit).compile()
    return hlo_analysis.launch_count(compiled.as_text())


def run() -> None:
    import jax

    from repro import configs
    from repro.models import transformer
    from repro.serving import ServingEngine, StaticServingEngine

    cfg = configs.get_smoke_config(ARCH)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)

    records = [
        _engine_record("static", StaticServingEngine(
            cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ), cfg),
        _engine_record("continuous", ServingEngine(
            cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
            page_size=PAGE_SIZE), cfg),
    ]
    launches = {
        "decode_launches_flash": _decode_launches(cfg, params,
                                                  use_flash=True),
        "decode_launches_ref": _decode_launches(cfg, params,
                                                use_flash=False),
    }
    out = {
        "workload": {"arch": ARCH, "max_batch": MAX_BATCH,
                     "max_seq": MAX_SEQ, "page_size": PAGE_SIZE,
                     "n_requests": N_REQUESTS, "budgets": BUDGETS,
                     "seed": SEED},
        "records": records,
        **launches,
    }
    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=1)
    for r in records:
        print(f"serve/{r['case']},{1e6 / max(r['tok_s'], 1e-9):.1f},"
              f"tok_s={r['tok_s']} p95_ms={r['p95_ms']} "
              f"steps={r['decode_steps']}")
    print(f"serve/launches,0,flash={launches['decode_launches_flash']} "
          f"ref={launches['decode_launches_ref']}")
    print(f"# wrote {OUT_PATH}")


if __name__ == "__main__":
    run()
