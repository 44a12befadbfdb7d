"""Production training launcher.

One process drives every local device; by default each device is one
SDM-DSGD node on the ``data`` axis. Selects architecture / algorithm /
gossip parameters from the CLI and runs the distributed SDM-DSGD train
step built by ``repro.train.steps.make_distributed_train``. ``train``
takes the ``ModelConfig`` itself, so a caller can hand it a cut of a
published config (``chip_smoke.py`` does).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --smoke \
      --steps 5 --mesh 1x2            # reduced config, 2-device debug mesh
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, List, Optional, Sequence


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--mesh", default="local",
                    help="local (every local device on the data axis, one "
                         "node each) | N | DxM | PxDxM | single_pod | "
                         "multi_pod")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--method", default=None,
                    help="method registry name (repro.core.method): "
                         "sdm-dsgd | sdm-dsgd-fused | dc-dsgd | dsgd | "
                         "gradient-push | allreduce")
    ap.add_argument("--algorithm", default=None,
                    help="deprecated alias of --method")
    ap.add_argument("--p", type=float, default=0.2)
    ap.add_argument("--theta", type=float, default=0.5)
    ap.add_argument("--gamma", type=float, default=1e-2)
    ap.add_argument("--sigma", type=float, default=0.0)
    ap.add_argument("--clip-c", type=float, default=None)
    ap.add_argument("--gossip-mode", default="bernoulli",
                    choices=["bernoulli", "fixedk_packed", "fixedk_rows",
                             "qsgd"],
                    help="legacy wire-format spelling: fixedk_packed keeps "
                         "single coordinates (fixedk:1), fixedk_rows whole "
                         "plane rows; --compressor overrides it")
    ap.add_argument("--compressor", default=None,
                    help="wire compressor spec (repro.core.compressor): "
                         "bernoulli | fixedk[:block] | block:<B> | rows | "
                         "qsgd[:bits] | qsgdf[:bits] (fused single-buffer "
                         "quantizer, bits in {2,4,8}); overrides "
                         "--gossip-mode; for gradient-push switches on "
                         "error-compensated compressed push-sum")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped transport: exchange the next round's "
                         "wire planes under this round's compute "
                         "(one-step-stale neighbour mixing; static "
                         "topologies only — not matchings:<L>)")
    ap.add_argument("--topology", default="ring",
                    help="gossip graph over the node axis: ring | torus | "
                         "torusRxC | er | er:<p_c> | star | complete | "
                         "dring | der:<p_c> (directed, for gradient-push) | "
                         "matchings:<L> (time-varying random matchings) "
                         "(paper §5 uses er:0.35)")
    ap.add_argument("--topology-seed", type=int, default=0,
                    help="ER graph / matching sampling seed")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sim", default=None,
                    help="run under the event-driven edge-fleet simulator "
                         "instead of the lock-step distributed step: a "
                         "preset (no-fault | straggler | dropout | churn) "
                         "or a scenario spec like "
                         "'q=0.8,deadline=1.5,straggle=0.25x8,dropout=0.05,"
                         "churn=0.02:5' (see repro.sim.fleet)")
    ap.add_argument("--sim-rounds", type=int, default=None,
                    help="global rounds to simulate (defaults to --steps)")
    args = ap.parse_args(argv)
    if args.overlap and args.topology.startswith("matchings"):
        ap.error("--overlap needs a static topology: the double-buffered "
                 "transport has no replica (time-varying) delivery path")
    return args


@dataclasses.dataclass
class TrainRun:
    """What one lock-step training run produced (``train``'s result)."""
    mesh: Any
    tc: Any                    # steps.DistributedTrainConfig
    state: Any                 # final stacked state, one node per device
    compiled: Any              # the compiled train step (jax.stages)
    compile_s: float
    losses: List[float]
    step_s: List[float]        # per step, each ended by block_until_ready


def train(args: argparse.Namespace, cfg) -> Optional[TrainRun]:
    """Run ``args`` on the model ``cfg`` (a ``ModelConfig``); ``None``
    for the ``--sim`` axis, which reports through its own printout."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import save_checkpoint
    from repro.core import gossip, method as method_mod
    from repro.core.sdm_dsgd import SDMConfig, compressor_of
    from repro.data import TokenStream
    from repro.launch.compile_cache import compile_counts
    from repro.launch.mesh import make_mesh_by_name, node_axis_names
    from repro.models import moe
    from repro.train import steps as steps_mod

    meth_name = method_mod.normalize(
        args.method or args.algorithm or "sdm-dsgd")
    method_mod.get(meth_name)   # fail fast on unknown registrations
    mesh = make_mesh_by_name(args.mesh)
    node_axes = node_axis_names(mesh)
    n_nodes = 1
    for a in node_axes:
        n_nodes *= mesh.shape[a]

    batch = args.global_batch or 2 * n_nodes
    seq = args.seq_len or (64 if args.smoke else 4096)

    sdm_cfg = SDMConfig(p=args.p, theta=args.theta, gamma=args.gamma,
                        sigma=args.sigma, clip_c=args.clip_c,
                        mode=args.gossip_mode, compressor=args.compressor,
                        overlap=args.overlap)
    tc = steps_mod.DistributedTrainConfig(
        model=cfg,
        sdm=sdm_cfg,
        topology=args.topology,
        topology_seed=args.topology_seed,
        method=meth_name,
        param_dtype=jnp.float32 if args.smoke else jnp.bfloat16)
    sched = steps_mod.gossip_schedule(tc, mesh)
    mcfg = tc.resolved()[1]
    fixedk = isinstance(mcfg, SDMConfig) and compressor_of(
        mcfg).transport in ("blocks", "rows")
    # a node's own S(d) is a dense select over a mask-drawn keep set
    pack = " own_sdm=mask-select" if fixedk else ""

    banner = (f"arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"nodes={n_nodes} method={meth_name} p={args.p} theta={args.theta} "
              f"compressor={args.compressor or sdm_cfg.mode} "
              f"topology={sched.name} gossip_rounds={sched.n_rounds} "
              f"batch={batch} seq={seq}{pack}")
    tail = ((f" time_varying_L={sched.length}" if sched.length > 1 else "")
            + (" overlap=on" if args.overlap else ""))

    if args.sim:
        print(banner + tail, flush=True)
        _run_simulated(args, cfg, sdm_cfg, meth_name, n_nodes, batch, seq)
        return None

    state = steps_mod.init_distributed_state(tc, mesh,
                                             jax.random.PRNGKey(args.seed))
    stream = TokenStream(vocab_size=cfg.vocab_size, batch=batch, seq_len=seq,
                         seed=args.seed)
    on_nodes = steps_mod.batch_sharding(mesh)
    has_ctx = cfg.family in ("audio", "vlm")

    def step_args(t):
        tokens, labels = stream.batch_at(t)
        out = [jax.device_put(tokens, on_nodes),
               jax.device_put(labels, on_nodes)]
        if has_ctx:
            shape = (batch, cfg.encoder_seq if cfg.family == "audio"
                     else cfg.n_image_tokens, cfg.d_model)
            out.append(jax.device_put(
                jnp.full(shape, 0.01, tc.param_dtype), on_nodes))
        return out

    # the banner follows the step's tracing, so that it can say how many
    # compacted wire index lists the built step holds
    before = dict(compile_counts())
    drawn = dict(gossip.draw_counts())
    moe_before = dict(moe.layer_counts())
    t0 = time.perf_counter()
    # the state is donated: the step never holds two copies of it
    lowered = jax.jit(steps_mod.make_distributed_train(tc, mesh),
                      donate_argnums=0).lower(state, *step_args(0))
    if fixedk:
        now = gossip.draw_counts()
        banner += f" compact_draws={now['compact'] - drawn['compact']}"
    if cfg.has_moe:
        # traced MoE layer bodies (a scanned period's slot once), their
        # held and routed experts, and their grouped matmuls
        banner += " moe=" + ",".join(
            f"{k}:{v - moe_before[k]}" for k, v in moe.layer_counts().items())
    print(banner + tail, flush=True)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    cache = {k: round(v - before[k], 3) for k, v in compile_counts().items()}
    print(f"compiled train step in {compile_s:.2f}s (compile {cache})",
          flush=True)

    losses, step_s = [], []
    for t in range(args.steps):
        fn_args = step_args(t)
        t0 = time.perf_counter()
        state, loss, *rows = compiled(state, *fn_args)
        jax.block_until_ready((state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
        routed = f" moe_rows {int(rows[0])}" if rows else ""
        print(f"step {t:4d} loss {losses[-1]:.4f}{routed} "
              f"({step_s[-1]:.3f}s)", flush=True)

    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, args.steps, state)
        print(f"checkpoint written to {args.checkpoint_dir}")
    return TrainRun(mesh=mesh, tc=tc, state=state, compiled=compiled,
                    compile_s=compile_s, losses=losses, step_s=step_s)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)

    from repro import configs
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    train(args, cfg)


def _run_simulated(args, cfg, sdm_cfg, meth_name, n_nodes,
                   batch, seq) -> None:
    """The --sim axis: event-driven edge-fleet run on the reference
    executor (stacked single host), simulated wall-clock per round."""
    import jax
    import jax.numpy as jnp

    from repro.data import TokenStream
    from repro.models import transformer
    from repro.sim import Fleet, parse_scenario, simulate

    if cfg.family in ("audio", "vlm"):
        raise SystemExit("--sim supports text models only")
    if n_nodes < 2:
        raise SystemExit("--sim needs a >= 2-node mesh (e.g. --mesh 4x1)")

    rounds = args.sim_rounds or args.steps
    per_node = max(batch // n_nodes, 1)
    stream = TokenStream(vocab_size=cfg.vocab_size, batch=n_nodes * per_node,
                         seq_len=seq, seed=args.seed)
    params = transformer.init_params(jax.random.PRNGKey(args.seed), cfg,
                                     jnp.float32)
    stack = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_nodes,) + p.shape), params)

    def one_loss(p, tokens, labels):
        logits, aux = transformer.forward(p, cfg, tokens)
        return transformer.lm_loss(logits, labels, cfg.vocab_size, aux)

    def grad_fn(params_stack, batch_stack):
        tokens, labels = batch_stack
        losses, grads = jax.vmap(jax.value_and_grad(one_loss))(
            params_stack, tokens, labels)
        return grads, jnp.mean(losses)

    def batches():
        t = 0
        while True:
            tokens, labels = stream.batch_at(t)
            yield (jnp.asarray(tokens).reshape(n_nodes, per_node, -1),
                   jnp.asarray(labels).reshape(n_nodes, per_node, -1))
            t += 1

    spec = parse_scenario(args.sim)
    print("sim fleet: " + Fleet(n_nodes, spec, seed=args.seed).describe())
    res = simulate(topo=args.topology, algorithm=meth_name, sdm_cfg=sdm_cfg,
                   params_stack=stack, grad_fn=grad_fn, batches=batches(),
                   rounds=rounds, scenario=spec, seed=args.seed)
    r = res.result
    for t in range(len(r.losses)):
        print(f"round {t:4d} t_sim {r.sim_time_s[t]:9.3f}s "
              f"loss {r.losses[t]:.4f} "
              f"wire_bits {r.comm_bits[t]}", flush=True)
    print(f"sim done: rounds={res.rounds} t_sim={res.sim_seconds:.3f}s "
          f"stragglers={res.straggler_rounds} dropouts={res.dropout_rounds} "
          f"recompiles={res.recompiles} events={len(res.trace)}")


if __name__ == "__main__":
    main()
