"""Machine-readable wire-plane perf snapshot -> BENCH_perf.json.

Compiles one distributed step per (method, mode) case on an 8-node fake
CPU mesh with a MULTI-LEAF parameter tree and records the structural
quantities the wire-plane transport optimizes — the numbers future perf
PRs regress against:

    permutes_per_step   collective-permutes per compiled step (latency
                        serialization; == R per exchange on the plane
                        path, leaf-count-independent)
    sort_count          top-k/sort kernels per step (one batched draw
                        per plane, not per leaf/round)
    wire_bits_hlo       summed collective-permute payload bits per step
    wire_bits_acc       the static accounting's per-step prediction
    collective_bytes    hlo_analysis byte totals per step
    launches / fusion_factor
                        kernel-launch proxy (``hlo_analysis.launch_count``:
                        opcode-PARSED fusions + custom-calls + sorts +
                        collectives, async pairs counted once at the
                        ``-start``) and instructions-per-launch —
                        HLO-structural, CPU wall time is not TPU-indicative
    permute_starts / permute_dones
                        async collective-permute pair counts, reported
                        DISTINCTLY (both 0 when the scheduler emits the
                        sync form)
    overlap_efficiency  fraction of wire time hidden under compute for
                        the one-step-stale overlapped transport, under
                        the nominal edge-fleet machine model (cost-
                        analysis flops / permute payload bytes); 0.0 by
                        definition for overlap=off

Wall-clock is deliberately NOT recorded: this container runs interpret-
mode CPU; the HLO structure is the portable signal.

``benchmarks/baselines/perf_wire.json`` pins the snapshot CI regresses
against (``python -m benchmarks.check_perf``): launches and
permutes_per_step may not grow past threshold, and the fused wire paths
(qsgdf, the pallas gather-pack) must stay strictly below their unfused
counterparts.

Run via ``python -m benchmarks.run --only perf`` (writes BENCH_perf.json
at the repo root; CI uploads it as an artifact) or directly:
``python -m benchmarks.perf_wire``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

OUT_PATH = os.environ.get("BENCH_PERF_OUT", "BENCH_perf.json")

CASES = [
    # (method, topology, mode, overlap)
    ("sdm-dsgd", "ring", "fixedk_packed", False),
    ("sdm-dsgd", "ring", "bernoulli", False),
    ("sdm-dsgd", "ring", "qsgd:4", False),
    ("sdm-dsgd-fused", "ring", "fixedk_rows", False),
    ("dsgd", "ring", "-", False),
    ("gradient-push", "dring", "fixedk", False),
    # fused single-buffer quantizer: 1 payload leaf, 1 pallas pack
    # launch — must beat qsgd:4 on launches AND permutes_per_step
    ("sdm-dsgd", "ring", "qsgdf:4", False),
    # overlapped one-step-stale transport: same wire, hidden latency
    ("sdm-dsgd", "ring", "fixedk_packed", True),
    ("sdm-dsgd", "ring", "qsgdf:4", True),
]

# nominal edge-fleet machine model for the overlap_efficiency estimate
# (matches sim/fleet bandwidth scale): compute throughput and wire
# bandwidth used to convert HLO flops / payload bytes into time.
NOMINAL_FLOPS_PER_S = 1.0e12
NOMINAL_WIRE_BYTES_PER_S = 1.25e9          # 10 Gb/s edge uplink


def case_id(meth_name: str, topo_spec: str, mode: str,
            overlap: bool) -> str:
    return f"{meth_name}/{topo_spec}/{mode}" + ("+ov" if overlap else "")

# multi-leaf tree (the leaf-count-independence witness)
PARAM_SHAPES = {"emb": (9, 33), "w1": (64, 7), "b1": (71,),
                "w2": (3, 5, 11), "b2": (13,)}


def _emit() -> None:
    """Subprocess body: needs XLA_FLAGS set BEFORE jax import."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, PartitionSpec as P

    from repro.core import (baselines, gossip, gradient_push,
                            method as method_mod, plane as plane_mod,
                            sdm_dsgd, topology)
    from repro.launch import hlo_analysis

    n = 8
    records = []
    for meth_name, topo_spec, mode, overlap in CASES:
        meth = method_mod.get(meth_name)
        topo = topology.directed_ring(n) if topo_spec == "dring" \
            else topology.by_name(topo_spec, n)
        seq = gossip.ensure_sequence(gossip.schedule_from_topology(topo))
        if meth.config_cls is sdm_dsgd.SDMConfig:
            kw = dict(p=0.25, theta=0.15, gamma=0.1, overlap=overlap)
            cfg = meth.coerce_config(sdm_dsgd.SDMConfig(
                **(dict(kw, compressor=mode)
                   if mode.split(":")[0] in ("qsgd", "qsgdf")
                   else dict(kw, mode=mode))))
        elif meth.config_cls is gradient_push.GradientPushConfig:
            cfg = gradient_push.GradientPushConfig(
                gamma=0.1, compressor=None if mode == "-" else mode,
                p=0.25, overlap=overlap)
        else:
            cfg = baselines.DSGDConfig(gamma=0.1)

        rng = np.random.default_rng(0)
        is_shape = lambda v: isinstance(v, tuple) and all(
            isinstance(e, int) for e in v)
        p0 = jax.tree.map(
            lambda s: jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32),
            PARAM_SHAPES, is_leaf=is_shape)
        stack = jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (n,) + v.shape), p0)

        mesh = jax.make_mesh((n,), ("data",),
                             axis_types=(AxisType.Auto,))
        ex = meth.make_distributed(seq, cfg, "data")
        key = jax.random.PRNGKey(0)

        def one_step(stack):
            def inner(p):
                p = jax.tree.map(lambda v: jnp.squeeze(v, 0), p)
                me = jax.lax.axis_index("data")
                state = ex.init(p, me)

                # scan >= 2 steps so the exchanged differential is
                # data-dependent — XLA folds away collectives whose
                # operand is the constant-zero d_0 of a single unrolled
                # first step, which would under-count permutes/step.
                def body(state, _):
                    state, _ = ex.step(
                        state,
                        lambda pp: (jax.tree.map(lambda v: v * 0.01, pp),
                                    0.0),
                        base_key=key)
                    return state, None

                state, _ = jax.lax.scan(body, state, None, length=2)
                return jax.tree.map(lambda v: v[None], state.x)

            return jax.shard_map(inner, mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P("data"), axis_names={"data"},
                                 check_vma=False)(stack)

        compiled = jax.jit(one_step).lower(stack).compile()
        hlo = compiled.as_text()
        payloads = hlo_analysis.permute_payloads(hlo)

        per_node = p0
        spec = plane_mod.ParamPlane.for_tree(per_node)
        if meth.config_cls is sdm_dsgd.SDMConfig:
            acc_bits = sdm_dsgd.transmitted_bits_per_step(per_node, cfg,
                                                          seq=seq)
        else:
            acc_bits = method_mod.transmitted_bits(meth, per_node, cfg,
                                                   seq=seq)
        # opcode-PARSED counts (the old string-match heuristic counted
        # operand references and fused-computation names as launches)
        instr = hlo_analysis.instruction_counts(hlo)
        n_instr = sum(instr.values())
        sorts = instr.get("sort", 0)
        launches = hlo_analysis.launch_count(hlo)
        pairs = hlo_analysis.async_collective_pairs(hlo).get(
            "collective-permute", {"sync": 0, "start": 0, "done": 0})

        # model-based overlap efficiency: wall time on this CPU host is
        # not TPU-indicative, so convert the compiled module's flops and
        # permute payload bytes into time under the nominal machine
        # model. With overlap the per-step wire cost is what compute
        # cannot hide: efficiency = min(1, t_compute / t_wire).
        wire_bytes = max(sum(p["bytes"] for p in payloads), 1)
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            flops = float(ca.get("flops", 0.0))
        except Exception:
            flops = 0.0
        flops = max(flops, float(n_instr))     # floor: never a 0 proxy
        t_compute = flops / NOMINAL_FLOPS_PER_S
        t_wire = wire_bytes / NOMINAL_WIRE_BYTES_PER_S
        overlap_eff = round(min(1.0, t_compute / t_wire), 4) \
            if overlap else 0.0

        records.append({
            "case": case_id(meth_name, topo_spec, mode, overlap),
            "overlap": overlap,
            "n_leaves": len(jax.tree.leaves(stack)),
            "plane_shapes": spec.plane_shapes(),
            "schedule_rounds": seq.schedules[0].n_rounds,
            "permutes_per_step": hlo_analysis.collective_permute_count(hlo),
            "permute_starts": pairs["start"],
            "permute_dones": pairs["done"],
            "sort_count": sorts,
            "wire_bits_hlo": sum(p["bits"] for p in payloads),
            "wire_bits_acc": acc_bits,
            "collective_bytes": hlo_analysis.collective_bytes(hlo),
            "hlo_instructions": n_instr,
            "launches": launches,
            "fusion_factor": round(n_instr / max(launches, 1), 2),
            "overlap_efficiency": overlap_eff,
        })
    print("BENCH_PERF_JSON " + json.dumps(
        {"n_nodes": n, "records": records}))


def run(out_path: str = OUT_PATH) -> dict:
    from benchmarks import common

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # the child counts HLO on faked CPU devices; it must never reach for a
    # chip the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.perf_wire", "--emit"],
        capture_output=True, text=True, env=env, timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(f"perf_wire subprocess failed:\n{out.stderr[-3000:]}")
    payload = next(line for line in out.stdout.splitlines()
                   if line.startswith("BENCH_PERF_JSON "))
    data = json.loads(payload[len("BENCH_PERF_JSON "):])
    with open(out_path, "w") as f:
        json.dump(data, f, indent=2)
    for rec in data["records"]:
        common.emit(
            "perf_wire_" + rec["case"].replace("/", "_"), 0.0,
            f"permutes/step={rec['permutes_per_step']};"
            f"rounds={rec['schedule_rounds']};"
            f"n_leaves={rec['n_leaves']};sorts={rec['sort_count']};"
            f"wire_bits_hlo={rec['wire_bits_hlo']};"
            f"wire_bits_acc={rec['wire_bits_acc']};"
            f"launches={rec['launches']};"
            f"perm_start={rec['permute_starts']};"
            f"perm_done={rec['permute_dones']};"
            f"overlap_eff={rec['overlap_efficiency']};"
            f"fusion_factor={rec['fusion_factor']}")
    print(f"# wrote {out_path}")
    return data


if __name__ == "__main__":
    if "--emit" in sys.argv:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        _emit()
    else:
        run()
