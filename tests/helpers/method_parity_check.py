"""Subprocess helper: reference executor == shard_map distributed executor
for every registered method, on any topology (static / directed /
time-varying), dense and packed payloads — the table-driven sweep behind
tests/test_distributed.py.

Run with 8 fake host devices; prints per-case lines

    CASE <id> MAXERR <f> SCALE <f> HAS_CPERM <b> [WIRE_ELEMS <i>
         EXPECTED_WIRE_ELEMS <i> SORT_COUNT <i> MAX_SORTS <i> ...]

that the test asserts on. Must set XLA_FLAGS before jax import.

All wire expectations are PLANE-aware (PR 5): the transport compresses
the zero-padded (rows, LANE) wire plane of the whole differential, so
payload sizes, top-k counts, and accounting derive from the plane
geometry (``repro.core.plane``), not per-leaf shapes. The ``plane``
group runs a MULTI-LEAF parameter tree and asserts the tentpole
acceptance criterion: the compiled step carries exactly R
collective-permutes per exchange — leaf-count-independent — and the
static wire-bit accounting equals the HLO payload bits (including the
packed sub-byte qsgd u8 lanes).

Usage: method_parity_check.py GROUP     (GROUP in CASES)
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import re  # noqa: E402
import sys  # noqa: E402
from fractions import Fraction  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, PartitionSpec as P  # noqa: E402

from repro.core import (baselines, gossip, gradient_push, method as  # noqa: E402
                        method_mod, plane as plane_mod, sdm_dsgd,  # noqa: E402
                        sparsifier, topology)  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402

# One wire-plane row-count > 1 (DIM = 5 * plane.LANE, so the padded plane
# IS the tree: accounting, payload, and legacy intuitions coincide while
# rows-mode top-k still selects among 5 rows).
DIM = 5 * plane_mod.LANE
STEPS = 12
BASE_KEY = jax.random.PRNGKey(42)

# (method, topology spec, gossip mode) — mode "-" for full-state methods.
# "sdm-dsgd:het" marks the heterogeneous per-node-p variant. For
# gradient-push a non-"-" mode is a COMPRESSOR SPEC (repro.core.compressor):
# the error-compensated compressed push-sum variant rides the generic
# exchange_payload transport. "qsgd" cases exercise the int8 quantizer,
# "qsgd:4" the u8-packed sub-byte wire.
CASES = {
    "sdm_core": [
        ("sdm-dsgd", "ring8", "bernoulli"),
        ("sdm-dsgd", "ring8", "fixedk_packed"),
        ("sdm-dsgd", "ring8", "fixedk_rows"),
        ("sdm-dsgd", "torus2x2", "bernoulli"),
        ("sdm-dsgd", "torus2x2", "fixedk_packed"),
        ("sdm-dsgd", "er8", "fixedk_packed"),
        ("sdm-dsgd", "star4", "bernoulli"),
    ],
    "sdm_variants": [
        ("sdm-dsgd-fused", "ring8", "fixedk_rows"),
        ("sdm-dsgd-fused", "torus2x2", "fixedk_packed"),
        ("dc-dsgd", "torus2x2", "bernoulli"),
        ("dc-dsgd", "ring8", "fixedk_packed"),
        ("sdm-dsgd", "matchings8x3", "bernoulli"),
        ("sdm-dsgd", "matchings8x3", "fixedk_packed"),
        ("sdm-dsgd:het", "ring8", "bernoulli"),
    ],
    "baselines": [
        ("dsgd", "ring8", "-"),
        ("dsgd", "er8", "-"),
        ("dsgd", "matchings8x3", "-"),
        ("gradient-push", "dring8", "-"),
        ("gradient-push", "der8", "-"),
        ("allreduce", "ring8", "-"),
        ("allreduce", "er8", "-"),
    ],
    "compressed": [
        ("gradient-push", "dring8", "bernoulli"),
        ("gradient-push", "dring8", "fixedk"),
        ("gradient-push", "der8", "fixedk"),
        ("gradient-push", "der8", "qsgd"),
        ("gradient-push", "dring8", "qsgdf:4"),
        ("sdm-dsgd", "ring8", "qsgd"),
        ("sdm-dsgd", "ring8", "qsgd:4"),
        ("sdm-dsgd:het", "ring8", "fixedk_packed"),
        ("sdm-dsgd:het", "torus2x2", "fixedk_packed"),
    ],
    # Replica-correct time-varying gossip: genuinely varying W(t) runs the
    # union-graph replica transport. SDM cases additionally check the
    # reference against an EXPLICIT dense W(t) oracle (no incremental
    # state); compressed gradient-push cases additionally check the
    # sum x / sum w mass-conservation invariant on P(t); all cases check
    # per-link schedule-aware wire accounting against the HLO payload.
    "time_varying": [
        ("sdm-dsgd", "matchings8x2", "bernoulli"),
        ("sdm-dsgd", "matchings8x2", "fixedk_packed"),
        ("sdm-dsgd", "matchings8x2", "qsgd"),
        ("sdm-dsgd-fused", "matchings8x2", "fixedk_packed"),
        ("gradient-push", "matchings8x2", "bernoulli"),
        ("gradient-push", "matchings8x2", "fixedk"),
        ("gradient-push", "matchings8x2", "qsgd"),
    ],
    # The wire-plane tentpole: a MULTI-LEAF tree (5 leaves, padded plane)
    # must compile to exactly R collective-permutes per exchange, with
    # HLO payload bits equal to the static accounting (fixedk + packed
    # sub-byte qsgd), while reference<->distributed parity holds.
    "plane": [
        ("sdm-dsgd", "ring8", "fixedk_packed"),
        ("sdm-dsgd", "star4", "bernoulli"),
        ("sdm-dsgd-fused", "ring8", "fixedk_rows"),
        ("sdm-dsgd", "ring8", "qsgd:4"),
        ("sdm-dsgd", "ring8", "qsgdf:4"),
        ("dsgd", "ring8", "-"),
        ("gradient-push", "dring8", "fixedk"),
    ],
    # OVERLAPPED transport (":ov" = cfg.overlap=True): one-step-stale
    # neighbour mixing with the wire exchanged under compute. Parity must
    # hold reference<->distributed, the SDM reference must equal the
    # EXPLICIT delayed-mixing dense oracle, and the trajectory must
    # genuinely DIVERGE from overlap=off (the staleness is real, not a
    # no-op flag).
    "overlap": [
        ("sdm-dsgd:ov", "ring8", "bernoulli"),
        ("sdm-dsgd:ov", "ring8", "fixedk_packed"),
        ("sdm-dsgd:ov", "ring8", "qsgd:4"),
        ("sdm-dsgd:ov", "ring8", "qsgdf:4"),
        ("sdm-dsgd-fused:ov", "ring8", "fixedk_packed"),
        ("gradient-push:ov", "dring8", "fixedk"),
    ],
}

# Multi-leaf parameter tree for the "plane" group: mixed ranks/sizes,
# total 994 elements -> one (8, 128) plane with 30 pad zeros.
PLANE_SHAPES = {"emb": (9, 33), "w1": (64, 7), "b1": (71,),
                "w2": (3, 5, 11), "b2": (13,)}


def parse_seq(spec: str) -> gossip.ScheduleSequence:
    m = re.fullmatch(r"matchings(\d+)x(\d+)", spec)
    if m:
        n, rounds = int(m.group(1)), int(m.group(2))
        return gossip.sequence_from_topologies(
            topology.random_matchings(n, rounds, seed=0),
            name=spec)
    m = re.fullmatch(r"([a-z]+)(\d+(?:x\d+)?)", spec)
    family, size = m.group(1), m.group(2)
    if family == "torus":
        rows, cols = (int(v) for v in size.split("x"))
        topo = topology.torus_2d(rows, cols)
    else:
        topo = topology.by_name(family, int(size))
    return gossip.ensure_sequence(gossip.schedule_from_topology(topo))


def make_cfg(meth_key: str, meth, mode: str, n: int):
    overlap = meth_key.endswith(":ov")
    if meth.config_cls is sdm_dsgd.SDMConfig:
        p = tuple(0.15 + 0.05 * (i % 4) for i in range(n)) \
            if meth_key.endswith(":het") else 0.25
        if mode.startswith("qsgd:") or mode.split(":")[0] == "qsgdf":
            return meth.coerce_config(sdm_dsgd.SDMConfig(
                p=p, theta=0.15, gamma=0.2, sigma=0.0, clip_c=1.0,
                compressor=mode, overlap=overlap))
        return meth.coerce_config(sdm_dsgd.SDMConfig(
            p=p, theta=0.15, gamma=0.2, sigma=0.0, clip_c=1.0, mode=mode,
            overlap=overlap))
    if meth.config_cls is gradient_push.GradientPushConfig:
        # a non-"-" mode is a compressor spec: the error-compensated
        # compressed push-sum variant
        return gradient_push.GradientPushConfig(
            gamma=0.2, compressor=None if mode == "-" else mode, p=0.25,
            overlap=overlap)
    return baselines.DSGDConfig(gamma=0.2)


def debias(meth_name: str, x_tree, state):
    if meth_name == "gradient-push":
        return gradient_push._debias(x_tree, state.w)
    return x_tree


def sdm_oracle_x(seq, cfg, params_stack, a_stack, b_stack, node_grad,
                 steps: int) -> np.ndarray:
    """EXPLICIT dense W(t) simulator (the shared ``dense_oracle`` helper):
    no incremental state whatsoever — the acceptance oracle the
    replica-correct reference must match bit-comparably (<= 1e-6)."""
    from dense_oracle import sdm_dense_wt_oracle   # sibling module

    grad_stack = lambda x: jax.vmap(
        lambda w, a, b: node_grad(w, a, b)["w"])(x, a_stack, b_stack)
    return sdm_dense_wt_oracle(seq, cfg, params_stack["w"], grad_stack,
                               steps, BASE_KEY)


def push_conservation_probe(seq, mode: str) -> "tuple[float, float]":
    """(mass_err, z_err) of compressed push-sum PURE GOSSIP on ``seq``.

    gamma=0, sigma=0: sum x / sum w must stay the exact initial mean at
    every step (mass conservation on time-varying P(t)) and every node's
    de-biased estimate must converge to it.
    """
    cfg = gradient_push.GradientPushConfig(
        gamma=0.0, sigma=0.0, compressor=mode, p=0.4)
    sim = method_mod.get("gradient-push").make_reference(seq, cfg)
    rng = np.random.default_rng(5)
    stack = {"w": jnp.asarray(rng.normal(size=(seq.n_nodes, 6)), jnp.float32)}
    mean0 = np.mean(np.asarray(stack["w"]), axis=0)
    state = sim.init(stack)
    zero_grad = lambda p, b: (jax.tree.map(jnp.zeros_like, p), 0.0)
    key = jax.random.PRNGKey(0)
    step = jax.jit(lambda s, k: sim.step(s, zero_grad, None, k))
    mass_err = 0.0
    for _ in range(200):
        key, sub = jax.random.split(key)
        state, _ = step(state, sub)
        cons = np.asarray(sim.consensus(state)["w"])
        mass_err = max(mass_err, float(np.max(np.abs(cons - mean0))))
    z = np.asarray(sim.eval_params(state)["w"])
    return mass_err, float(np.max(np.abs(z - mean0)))


def plane_payload_expectations(spec_plane, mode: str, cfg):
    """(expected max f32 payload elems, blocks) at plane granularity."""
    (rows, lane), = spec_plane.plane_shapes()
    if mode == "fixedk_rows":
        return sparsifier.num_kept(rows, cfg.p) * lane
    d = rows * lane
    p_worst = max(cfg.p) if isinstance(cfg.p, tuple) else cfg.p
    block = getattr(cfg, "pack_block", 1)
    nb = -(-d // block)
    return sparsifier.num_kept(nb, p_worst) * block


# The permute-count contract lives with the static auditor now; the
# parity sweep asserts the SAME expectation the lint matrix enforces.
from repro.analysis.wire_audit import expected_permutes  # noqa: E402


def run_case(meth_key: str, topo_spec: str, mode: str,
             param_shapes=None, group: str = "") -> None:
    case_id = f"{meth_key}/{topo_spec}/{mode}"
    meth_name = meth_key.split(":")[0]
    meth = method_mod.get(meth_name)
    seq = parse_seq(topo_spec)
    n = seq.n_nodes
    cfg = make_cfg(meth_key, meth, mode, n)

    rng = np.random.default_rng(0)
    if param_shapes is None:
        # single-leaf least-squares problem (the historical anchor)
        a_stack = jnp.asarray(rng.normal(size=(n, 16, DIM)) / 4.0,
                              jnp.float32)
        b_stack = jnp.asarray(rng.normal(size=(n, 16)), jnp.float32)
        params0 = jnp.asarray(rng.normal(size=(DIM,)) * 0.1, jnp.float32)
        params_stack = {"w": jnp.broadcast_to(params0, (n, DIM))}

        def node_grad(w, a, b):
            r = a @ w - b
            return {"w": a.T @ r / a.shape[0]}

        def grads_of(tree, a, b):
            return node_grad(tree["w"], a, b)
    else:
        # multi-leaf quadratic: grad = x - t_i (per-node targets), so
        # parity is meaningful on an arbitrary pytree.
        a_stack = jax.tree.map(
            lambda s: jnp.asarray(rng.normal(size=(n,) + s), jnp.float32),
            param_shapes,
            is_leaf=lambda v: isinstance(v, tuple) and all(
                isinstance(e, int) for e in v))
        b_stack = jnp.zeros((n, 1), jnp.float32)
        p0 = jax.tree.map(lambda t: 0.1 * t[0] + 0.05, a_stack)
        params_stack = jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (n,) + v.shape), p0)

        def grads_of(tree, targets, b):
            del b
            return jax.tree.map(jnp.subtract, tree, targets)

    def grad_fn_stacked(params, batch):
        del batch
        g = jax.vmap(grads_of)(params, a_stack, b_stack)
        return g, jnp.float32(0.0)

    # ---------------- reference executor -----------------------------
    sim = meth.make_reference(seq, cfg)
    state = sim.init(params_stack)
    sdm_like = hasattr(sim, "advance")
    for _ in range(STEPS):
        if sdm_like:
            # drive the two phases directly with the shared BASE_KEY so
            # sparsifier seeds match the distributed executor bit-for-bit
            state, _ = sim.advance(state, BASE_KEY)
            grads, _ = grad_fn_stacked(state.x, None)
            state = sim.commit(state, grads, BASE_KEY)
        else:
            state, _ = sim.step(state, grad_fn_stacked, None, BASE_KEY)
    if meth_name == "sdm-dsgd-fused":
        # the fused distributed state already folded in the NEXT advance
        state, _ = sim.advance(state, BASE_KEY)
    ref_x = jax.tree.map(np.asarray, debias(meth_name, state.x, state))

    # ---------------- distributed executor ---------------------------
    mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
    ex = meth.make_distributed(seq, cfg, "data")

    def dist_train(params_stack, a_st, b_st):
        def inner(p, a, b):
            p = jax.tree.map(lambda v: jnp.squeeze(v, 0), p)
            a = jax.tree.map(lambda v: jnp.squeeze(v, 0), a)
            b = jnp.squeeze(b, 0)
            me = jax.lax.axis_index("data")
            state = ex.init(p, me)

            def body(state, _):
                state, _ = ex.step(
                    state,
                    lambda pp: (grads_of(pp, a, b), jnp.float32(0.0)),
                    base_key=BASE_KEY)
                return state, None

            state, _ = jax.lax.scan(body, state, None, length=STEPS)
            z = debias(meth_name, state.x, state)
            return jax.tree.map(lambda v: v[None], z)

        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P("data"), P("data"), P("data")),
                             out_specs=P("data"), axis_names={"data"},
                             check_vma=False)(params_stack, a_st, b_st)

    compiled = jax.jit(dist_train).lower(params_stack, a_stack,
                                         b_stack).compile()
    dist_x = jax.tree.map(np.asarray,
                          compiled(params_stack, a_stack, b_stack))

    err = max(float(np.max(np.abs(d_ - r_)))
              for d_, r_ in zip(jax.tree.leaves(dist_x),
                                jax.tree.leaves(ref_x)))
    scale = max(float(np.max(np.abs(r_))) for r_ in jax.tree.leaves(ref_x))
    hlo = compiled.as_text()
    line = (f"CASE {case_id} MAXERR {err} SCALE {scale} "
            f"HAS_CPERM {'collective-permute' in hlo}")

    payloads = hlo_analysis.permute_payloads(hlo)
    per_node = jax.tree.map(lambda v: v[0], params_stack)
    spec_plane = plane_mod.ParamPlane.for_tree(per_node)
    (p_rows, p_lane), = spec_plane.plane_shapes()
    plane_elems = p_rows * p_lane

    if mode in ("fixedk_packed", "fixedk_rows"):
        payload = max((pl["elems"].get("f32", 0) for pl in payloads),
                      default=0)
        # PLANE-granular payload: one top-k over the whole padded plane
        kb = plane_payload_expectations(spec_plane, mode, cfg)
        # Satellite check: ONE batched sender top_k per (plane, branch) +
        # one for the node's own indices — not one sort per shift round,
        # not one per pytree leaf. The replica transport is branch-free.
        max_sorts = 2 if gossip.needs_replicas(seq) else 1 + seq.length
        sorts = hlo.count(" sort(") + hlo.count(" sort.")
        line += (f" WIRE_ELEMS {payload} EXPECTED_WIRE_ELEMS {kb}"
                 f" SORT_COUNT {sorts} MAX_SORTS {max_sorts}")
    elif mode.split(":")[0] in ("fixedk", "block", "qsgd", "qsgdf"):
        # compressed gradient-push / sdm qsgd: the exchange_payload
        # transport. Assert the largest single wire payload stays at the
        # compressed size: k*32 value bits for fixed-k (indices ship as a
        # separate s32 leaf — the explicit index overhead), bits/coord
        # (u8-PACKED below a byte) for the quantizer. (bernoulli ships
        # the dense masked plane, nothing to bound.)
        max_bits = max((pl["bits"] for pl in payloads), default=0)
        base = mode.split(":")[0]
        if base == "qsgd":
            qbits = int(mode.split(":")[1]) if ":" in mode else 8
            factor = 8 // qbits if qbits in (2, 4) else 1
            exp_bits = (-(-plane_elems // factor)) * factor * qbits \
                if factor > 1 else plane_elems * qbits
        elif base == "qsgdf":
            # fused single-buffer format: packed bytes + the 4 norm
            # tail bytes ride ONE u8 permute
            qbits = int(mode.split(":")[1]) if ":" in mode else 4
            factor = 8 // qbits if qbits in (2, 4) else 1
            exp_bits = (-(-plane_elems // factor) + 4) * 8
        else:
            nb = plane_elems
            exp_bits = sparsifier.num_kept(nb, 0.25) * 32
        line += f" WIRE_BITS {max_bits} MAX_WIRE_BITS {exp_bits}"

    if group == "plane":
        # tentpole acceptance: exactly R permutes per exchange,
        # leaf-count-independent, and (for value-payload transports)
        # accounting == HLO payload bits.
        cperm = hlo_analysis.collective_permute_count(hlo)
        line += (f" CPERM {cperm}"
                 f" EXPECTED_CPERM {expected_permutes(meth_name, mode, seq)}"
                 f" N_LEAVES {len(jax.tree.leaves(params_stack))}")
        if meth_name.startswith("sdm-dsgd") and mode != "bernoulli":
            hlo_bits = sum(pl["bits"] for pl in payloads)
            acc_bits = sdm_dsgd.transmitted_bits_per_step(
                per_node, cfg, seq=seq)
            line += f" HLO_BITS {hlo_bits} ACC_BITS {acc_bits}"
        if meth_name == "dsgd":
            hlo_bits = sum(pl["bits"] for pl in payloads)
            acc_bits = method_mod.transmitted_bits(meth, per_node, cfg,
                                                   seq=seq)
            line += f" HLO_BITS {hlo_bits} ACC_BITS {acc_bits}"

    if group == "overlap":
        # the double buffer reuses the same exchange one step early, so
        # the permute count must NOT grow vs the non-overlapped step
        cperm = hlo_analysis.collective_permute_count(hlo)
        line += (f" CPERM {cperm} EXPECTED_CPERM "
                 f"{expected_permutes(meth_name, mode, seq)}")
        # the staleness is real: same seed, overlap off, must diverge
        cfg_off = make_cfg(meth_key[:-3], meth, mode, n)
        sim_off = meth.make_reference(seq, cfg_off)
        st = sim_off.init(params_stack)
        for _ in range(STEPS):
            if hasattr(sim_off, "advance"):
                st, _ = sim_off.advance(st, BASE_KEY)
                g_off, _ = grad_fn_stacked(st.x, None)
                st = sim_off.commit(st, g_off, BASE_KEY)
            else:
                st, _ = sim_off.step(st, grad_fn_stacked, None, BASE_KEY)
        if meth_name == "sdm-dsgd-fused":
            st, _ = sim_off.advance(st, BASE_KEY)
        off_x = jax.tree.map(np.asarray, debias(meth_name, st.x, st))
        div = max(float(np.max(np.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(off_x),
                                  jax.tree.leaves(ref_x)))
        line += f" STALE_DIVERGENCE {div}"
        if meth_name == "sdm-dsgd":
            # the reference must equal the EXPLICIT delayed-mixing oracle
            from dense_oracle import sdm_dense_overlap_oracle   # sibling

            grad_stack = lambda x: jax.vmap(
                lambda w, a, b: node_grad(w, a, b)["w"])(x, a_stack,
                                                         b_stack)
            ox = sdm_dense_overlap_oracle(seq, cfg, params_stack["w"],
                                          grad_stack, STEPS, BASE_KEY)
            line += (f" ORACLE_MAXERR "
                     f"{float(np.max(np.abs(ox - ref_x['w'])))}")

    if seq.length > 1 and mode != "-":
        # ---- replica-correct time-varying checks ----------------------
        useq = gossip.union_schedule(seq)
        union_deg = Fraction(sum(len(r.perm) for r in useq.rounds), n)
        round_deg = Fraction(
            sum(sum(len(r.perm) for r in s.rounds) for s in seq.schedules),
            n * seq.length)
        base_mode = mode.split(":")[0]
        if base_mode in ("fixedk", "block") or \
                mode in ("fixedk_packed", "fixedk_rows"):
            pay = sparsifier.num_kept(plane_elems, 0.25)
        elif base_mode == "qsgd":
            pay = plane_elems
        else:                      # bernoulli: informative expectation p*d
            pay = Fraction(repr(0.25)) * plane_elems
        # schedule-aware per-link accounting vs an independent
        # re-derivation: payload x union-degree (replica transport), plus
        # the mass scalar on the current-round graph for push-sum.
        acc = method_mod.transmitted_elements(meth, per_node, cfg, seq=seq)
        if meth_name == "gradient-push":
            exp_acc = round(pay * union_deg + round_deg)
        else:
            exp_acc = round(pay * union_deg)
        # ...and vs the HLO: the replica transport is switch-free, so the
        # compiled step must carry the payload over EXACTLY one
        # collective-permute per union round.
        if base_mode == "qsgd":
            pperms = sum(1 for pl in payloads
                         if pl["bits"] >= plane_elems * 8)
        elif isinstance(pay, Fraction):          # dense bernoulli payload
            pperms = sum(1 for pl in payloads
                         if pl["elems"].get("f32", 0) == plane_elems)
        else:
            pperms = sum(1 for pl in payloads
                         if pl["elems"].get("f32", 0) == pay)
        line += (f" ACC_ELEMS {acc} EXPECTED_ACC_ELEMS {exp_acc}"
                 f" PAYLOAD_PERMS {pperms} UNION_ROUNDS {useq.n_replicas}")
        if meth_name == "sdm-dsgd":
            # the reference must equal an EXPLICIT dense W(t) simulator
            ox = sdm_oracle_x(seq, cfg, params_stack, a_stack, b_stack,
                              node_grad, STEPS)
            line += f" ORACLE_MAXERR {float(np.max(np.abs(ox - ref_x['w'])))}"
        if meth_name == "gradient-push":
            m_err, z_err = push_conservation_probe(seq, mode)
            line += f" MASS_ERR {m_err} Z_ERR {z_err}"
    print(line, flush=True)


def main() -> None:
    group = sys.argv[1]
    for meth_key, topo_spec, mode in CASES[group]:
        run_case(meth_key, topo_spec, mode,
                 param_shapes=PLANE_SHAPES if group == "plane" else None,
                 group=group)


if __name__ == "__main__":
    main()
