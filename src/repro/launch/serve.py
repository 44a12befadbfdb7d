"""Serving launcher: continuous-batching greedy decoding.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --smoke \
      --requests 8 --max-new 12

Serve a trained decentralized checkpoint (the trainer's npz holds all n
node replicas; they are consensus-averaged into one model at load):

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-medium-14b \
      --smoke --checkpoint runs/ck --requests 8
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--checkpoint", default=None,
                    help="trainer checkpoint file or directory; the "
                         "stacked node replicas are consensus-averaged "
                         "into the serving model")
    ap.add_argument("--checkpoint-step", type=int, default=None)
    ap.add_argument("--engine", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt lengths in [1, prompt-len]")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--flash-decode", action="store_true",
                    help="route decode attention through the paged "
                         "pallas kernel off-TPU too (interpret mode); "
                         "on a TPU it is always on")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import transformer
    from repro.serving import Request, ServingEngine, StaticServingEngine
    from repro.serving.ingest import ingest_checkpoint

    use_compile_cache()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.checkpoint:
        params, report = ingest_checkpoint(args.checkpoint, cfg,
                                           step=args.checkpoint_step)
        print(report)
    else:
        params = transformer.init_params(jax.random.PRNGKey(args.seed), cfg)

    max_seq = args.prompt_len + args.max_new + 8
    if args.engine == "static":
        engine = StaticServingEngine(cfg, params,
                                     max_batch=args.max_batch,
                                     max_seq=max_seq)
    else:
        engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                               max_seq=max_seq, page_size=args.page_size,
                               use_flash=args.flash_decode or None)

    rng = np.random.default_rng(args.seed)
    reqs = []
    for _ in range(args.requests):
        plen = (int(rng.integers(1, args.prompt_len + 1)) if args.ragged
                else args.prompt_len)
        reqs.append(Request(
            prompt=rng.integers(0, cfg.vocab_size, size=plen).tolist(),
            max_new_tokens=args.max_new))

    context = None
    if cfg.family == "audio":
        context = jnp.full((args.max_batch, cfg.encoder_seq, cfg.d_model),
                           0.01, jnp.float32)
    elif cfg.family == "vlm":
        context = jnp.full((args.max_batch, cfg.n_image_tokens, cfg.d_model),
                           0.01, jnp.float32)

    t0 = time.time()
    engine.serve(reqs, context=context)
    dt = time.time() - t0
    total_new = sum(len(r.output) for r in reqs)
    print(f"served {len(reqs)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    stats = getattr(engine, "last_stats", None)
    if stats is not None:
        print(f"  kv pages peak {stats.pages_peak} / dense-equivalent "
              f"{stats.pages_dense_equiv}; prefills {stats.prefills}, "
              f"decode steps {stats.decode_steps}")
    for i, r in enumerate(reqs[:4]):
        print(f"  req{i}: prompt[:4]={r.prompt[:4]} -> out={r.output}")


if __name__ == "__main__":
    main()
