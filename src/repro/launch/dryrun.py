import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

For each combination this prints/records:
  * compiled.memory_analysis()  — per-device bytes (proves it fits),
  * compiled.cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective bytes parsed from the HLO (all-gather/all-reduce/
    reduce-scatter/all-to-all/collective-permute),
and writes one JSON per case under experiments/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                  # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
      --shape train_4k --mesh single_pod --algorithm sdm_dsgd
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback


def _memory_dict(mem) -> dict:
    out = {k: getattr(mem, k) for k in dir(mem)
           if k.endswith("_in_bytes") and not k.startswith("host_")}
    if "peak_memory_in_bytes" not in out:
        # older jaxlibs report only the component sizes; their sum upper-
        # bounds the true peak, which is what fits-on-device checks need.
        out["peak_memory_in_bytes"] = sum(
            out.get(k, 0) for k in ("argument_size_in_bytes",
                                    "output_size_in_bytes",
                                    "temp_size_in_bytes"))
    return out


def _probe_cfg(cfg, k: int):
    """Config with k unrolled periods (and k encoder layers) for exact
    cost probes — XLA counts while-loop bodies once, so the full-depth
    numbers are reconstructed as probe1 + (n_periods-1)*(probe2-probe1)."""
    kw = dict(n_layers=k * len(cfg.period), unroll_layers=True)
    if cfg.has_encoder:
        kw["n_encoder_layers"] = k
    return dataclasses.replace(cfg, **kw)


def build_case(arch: str, shape_name: str, mesh_name: str, method: str,
               gossip_mode: str, out_root: str, verbose: bool = True,
               probes: bool = True, sdm_overrides: dict | None = None,
               cfg_overrides: dict | None = None,
               rule_overrides: dict | None = None, smoke: bool = False,
               topology: str = "ring",
               compressor: str | None = None) -> dict:
    import jax

    from repro import configs
    from repro.core import method as method_mod
    from repro.launch import shapes as shapes_mod
    from repro.launch.mesh import make_mesh_by_name, node_axis_names

    method = method_mod.normalize(method)
    method_mod.get(method)   # unknown registrations fail before compiling
    case = shapes_mod.SHAPES[shape_name]
    cfg = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    skip = shapes_mod.skip_reason(cfg, case)
    if skip is not None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": skip}

    mesh = make_mesh_by_name(mesh_name)
    node_axes = node_axis_names(mesh)
    n_nodes = 1
    for a in node_axes:
        n_nodes *= mesh.shape[a]

    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "algorithm": method if case.kind == "train" else "serve",
              "n_devices": mesh.size, "status": "ok",
              "n_periods": cfg.n_periods}
    record.update(_measure(cfg, case, mesh, node_axes, method,
                           gossip_mode, shape_name, sdm_overrides,
                           rule_overrides=rule_overrides, topology=topology,
                           compressor=compressor))
    if probes:
        p1 = _measure(_probe_cfg(cfg, 1), case, mesh, node_axes, method,
                      gossip_mode, shape_name, sdm_overrides, cost_only=True,
                      rule_overrides=rule_overrides, topology=topology,
                      compressor=compressor)
        p2 = _measure(_probe_cfg(cfg, 2), case, mesh, node_axes, method,
                      gossip_mode, shape_name, sdm_overrides, cost_only=True,
                      rule_overrides=rule_overrides, topology=topology,
                      compressor=compressor)
        record["probe1"] = p1
        record["probe2"] = p2
    record["model_params"] = cfg.param_count()
    record["model_params_active"] = cfg.active_param_count()
    record["n_nodes"] = n_nodes
    record["per_node_batch"] = case.global_batch // max(n_nodes, 1) \
        if case.kind == "train" else None
    record["tokens_per_step"] = case.global_batch * case.seq_len \
        if case.kind == "train" else case.global_batch

    if verbose:
        print(f"[{arch} | {shape_name} | {mesh_name}] "
              f"compile={record['compile_s']}s "
              f"flops={record['flops']:.3e} "
              f"coll={record['collective_bytes'].get('total', 0):.3e}B")
        print("  memory:", record["memory"])

    if out_root:
        d = os.path.join(out_root, mesh_name, arch)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, f"{shape_name}.json"), "w") as f:
            json.dump(record, f, indent=2)
    return record


def _measure(cfg, case, mesh, node_axes, method: str, gossip_mode: str,
             shape_name: str, sdm_overrides: dict | None = None,
             cost_only: bool = False,
             rule_overrides: dict | None = None,
             topology: str = "ring",
             compressor: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.sdm_dsgd import SDMConfig
    from repro.launch import hlo_analysis, shapes as shapes_mod
    from repro.models import transformer
    from repro.sharding import MeshRules, tree_shardings
    from repro.train import steps as steps_mod

    record = {}
    t0 = time.time()
    if case.kind == "train":
        cfg = dataclasses.replace(cfg, remat=True)
        sdm_kw = dict(p=0.1, theta=0.25, gamma=1e-3, sigma=1.0,
                      clip_c=5.0, mode=gossip_mode, pack_block=1024,
                      compressor=compressor)
        sdm_kw.update(sdm_overrides or {})
        tc = steps_mod.DistributedTrainConfig(
            model=cfg, sdm=SDMConfig(**sdm_kw), method=method,
            topology=topology)
        step = steps_mod.make_distributed_train(tc, mesh)
        state_sds = steps_mod.state_shape_dtype(tc, mesh)
        state_shards = steps_mod.state_shardings(tc, mesh)
        specs = shapes_mod.input_specs(cfg, case)
        data_shard = jax.NamedSharding(
            mesh, jax.sharding.PartitionSpec(
                node_axes if len(node_axes) > 1 else node_axes[0]))
        args = [state_sds, specs["tokens"], specs["labels"]]
        in_sh = [state_shards, data_shard, data_shard]
        if "context" in specs:
            args.append(specs["context"])
            in_sh.append(data_shard)
        jf = jax.jit(step, in_shardings=tuple(in_sh))
        lowered = jf.lower(*args)
    else:
        rules_map = steps_mod.serving_rules(
            node_axes, shard_cache_seq=(shape_name == "long_500k"),
            decode=(case.kind == "decode"))
        rules_map.update(rule_overrides or {})
        rules = MeshRules(mesh, rules_map)
        specs = shapes_mod.input_specs(cfg, case)
        # params: bf16 serving weights sharded by logical axes
        pshapes = transformer.param_shapes(cfg)
        paxes = transformer.param_axes(cfg)
        is_shape = lambda v: isinstance(v, tuple) and all(
            isinstance(e, int) for e in v)
        params_sds = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(tuple(s), jnp.bfloat16), pshapes,
            is_leaf=is_shape)
        params_sh = tree_shardings(rules, paxes, pshapes)
        cache_axes = transformer.cache_logical_axes(cfg)
        cache_sh = jax.tree.map(
            lambda sds, ax: rules.sharding(ax, sds.shape) if ax != () else
            jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            specs["cache"], cache_axes,
            is_leaf=lambda v: isinstance(v, tuple) and all(
                isinstance(e, (str, type(None))) for e in v) or v == ())
        batch_sh = rules.sharding(("batch",), (case.global_batch,))

        if case.kind == "prefill":
            fn, _ = steps_mod.make_prefill_fn(
                cfg, mesh, shard_cache_seq=(shape_name == "long_500k"),
                rule_overrides=rule_overrides)
            args = [params_sds, specs["tokens"], specs["cache"]]
            in_sh = [params_sh,
                     rules.sharding(("batch", None),
                                    (case.global_batch, case.seq_len)),
                     cache_sh]
        else:
            fn, _ = steps_mod.make_decode_fn(
                cfg, mesh, shard_cache_seq=(shape_name == "long_500k"),
                rule_overrides=rule_overrides)
            args = [params_sds, specs["token"], specs["cache"]]
            in_sh = [params_sh, batch_sh, cache_sh]
        if "context" in specs:
            args.append(specs["context"])
            in_sh.append(rules.sharding(
                ("batch", None, None), specs["context"].shape))
        jf = jax.jit(fn, in_shardings=tuple(in_sh))
        lowered = jf.lower(*args)

    record["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t1, 2)

    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    record["flops"] = float(cost.get("flops", -1.0))
    record["bytes_accessed"] = float(cost.get("bytes accessed", -1.0))
    record["collective_bytes"] = hlo_analysis.collective_bytes(hlo)
    record["collective_ops"] = hlo_analysis.count_ops(hlo)
    if not cost_only:
        record["memory"] = _memory_dict(compiled.memory_analysis())
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single_pod,multi_pod")
    ap.add_argument("--method", default=None,
                    help="method registry name (repro.core.method); "
                         "legacy --algorithm spellings accepted")
    ap.add_argument("--algorithm", default=None,
                    help="deprecated alias of --method")
    ap.add_argument("--gossip-mode", default="fixedk_packed",
                    choices=["bernoulli", "fixedk_packed", "fixedk_rows",
                             "qsgd"])
    ap.add_argument("--compressor", default=None,
                    help="wire compressor spec (repro.core.compressor); "
                         "overrides --gossip-mode, reaches gradient-push")
    ap.add_argument("--topology", default="ring",
                    help="gossip graph spec (gossip.sequence_by_name)")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke configs (CI registration "
                         "smoke: compiles in seconds)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--keep-going", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip the unrolled cost-probe compiles")
    args = ap.parse_args()

    from repro import configs
    from repro.launch import shapes as shapes_mod

    method = args.method or args.algorithm or "sdm-dsgd"
    arches = sorted(configs.ALIASES) if args.arch == "all" \
        else args.arch.split(",")
    shape_names = list(shapes_mod.SHAPES) if args.shape == "all" \
        else args.shape.split(",")
    meshes = args.mesh.split(",")

    failures = []
    for mesh_name in meshes:
        for arch in arches:
            for shape_name in shape_names:
                try:
                    build_case(arch, shape_name, mesh_name, method,
                               args.gossip_mode, args.out,
                               probes=not args.no_probes,
                               smoke=args.smoke, topology=args.topology,
                               compressor=args.compressor)
                except Exception:
                    failures.append((arch, shape_name, mesh_name))
                    traceback.print_exc()
                    if not args.keep_going:
                        return 1
    if failures:
        print("FAILURES:", failures)
        return 1
    print("dry-run complete: all combinations lowered and compiled.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
