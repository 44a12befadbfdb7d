"""Registry-wide wire/privacy audit: compile every configuration, prove
the invariants, execute nothing.

For each ``AuditConfig`` in ``MATRIX`` (method x compressor x topology
on a 4-node host mesh) the auditor builds the same tiny least-squares
distributed train step the parity sweep uses, traces it to a jaxpr and
compiles it to HLO, then checks:

* **taint** (``jaxpr_taint``): privacy-claiming configs (sigma > 0 on a
  method that applies ``masked_grad``) must have NO un-sanitized
  data->collective path; known-non-private configs (``expect_taint``,
  e.g. allreduce's raw-gradient pmean, or sigma=0) must be FLAGGED —
  an empty report there means the analyzer lost its teeth, which is
  itself a failure.
* **prng** (``prng_lint``): no key reuse, no scan-invariant key, no
  kernel-padded draw shapes — on every config.
* **wire** (this module): ``collective_permute_count`` equals the
  schedule-derived expectation (leaf-count independence, PR 5); on
  static schedules the summed HLO permute payload bits equal the
  static accounting (``transmitted_bits``) exactly for deterministic
  wire formats; on time-varying schedules the payload-sized permutes
  equal the union-graph round count (the branch-free replica
  transport). The "every permute operand is Payload-derived" half is
  enforced at the jaxpr level by the taint pass's ``untagged-wire``
  rule (every operand must come through ``gossip._wire_ppermute``).

Needs >= 4 visible devices: run via ``python -m repro.analysis`` (which
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=...`` before
importing jax) or from a test subprocess that does the same.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.analysis import calibration, jaxpr_taint, prng_lint, sensitivity
from repro.core import (baselines, clipping, compressor as compressor_mod,
                        gossip, gradient_push, method as method_mod,
                        plane as plane_mod, privacy, sdm_dsgd, tagging,
                        topology)
from repro.kernels.sdm_update.sdm_update import LANE as KERNEL_LANE
from repro.launch import hlo_analysis

__all__ = ["AuditConfig", "MATRIX", "PASSES", "audit_config",
           "expected_permutes", "allowed_draw_shapes"]

#: every audit pass, in report order; ``--pass`` selects a subset.
PASSES = ("taint", "prng", "wire", "sensitivity", "calibration", "range",
          "overlap")

N_NODES = 4
DIM = 2 * plane_mod.LANE          # one (2, 128) wire plane
STEPS = 3                         # scan length: exercises the loop rules
BATCH = 8


@dataclasses.dataclass(frozen=True)
class AuditConfig:
    method: str                   # registry name ("sdm-dsgd", ...)
    topo: str                     # "ring4" | "dring4" | "matchings4x2"
    mode: str                     # gossip mode / compressor spec, "-" = dense
    sigma: float = 1.0
    expect_taint: bool = False    # True: the config is KNOWN non-private
    overlap: bool = False         # one-step-stale overlapped transport

    @property
    def id(self) -> str:
        tag = "dirty" if self.expect_taint else f"sigma{self.sigma:g}"
        mode = self.mode + "+ov" if self.overlap else self.mode
        return f"{self.method}/{self.topo}/{mode}/{tag}"


#: the audited registry sweep: every method, every compressor family,
#: static + directed + genuinely time-varying schedules — plus two
#: known-dirty negative controls proving the taint pass has teeth.
MATRIX: Tuple[AuditConfig, ...] = (
    AuditConfig("sdm-dsgd", "ring4", "bernoulli"),
    AuditConfig("sdm-dsgd", "ring4", "fixedk_packed"),
    AuditConfig("sdm-dsgd", "ring4", "fixedk_rows"),
    AuditConfig("sdm-dsgd", "ring4", "qsgd:8"),
    AuditConfig("sdm-dsgd", "ring4", "qsgd:4"),
    # fused single-buffer quantizer (kernels/wire_compress): 1 payload
    # leaf -> half the permutes of qsgd, same exact-bits contract
    AuditConfig("sdm-dsgd", "ring4", "qsgdf:4"),
    # overlapped one-step-stale transport: same permute count, same
    # payload bits, zero findings — staleness is a trajectory property,
    # not a wire property
    AuditConfig("sdm-dsgd", "ring4", "fixedk_packed", overlap=True),
    AuditConfig("sdm-dsgd", "ring4", "qsgdf:4", overlap=True),
    AuditConfig("gradient-push", "dring4", "fixedk", overlap=True),
    AuditConfig("sdm-dsgd", "matchings4x2", "bernoulli"),
    AuditConfig("sdm-dsgd", "matchings4x2", "fixedk_packed"),
    AuditConfig("sdm-dsgd-fused", "ring4", "fixedk_packed"),
    AuditConfig("sdm-dsgd-fused", "matchings4x2", "fixedk_packed"),
    AuditConfig("dc-dsgd", "ring4", "bernoulli"),
    AuditConfig("dsgd", "ring4", "-"),
    AuditConfig("dsgd", "matchings4x2", "-"),
    AuditConfig("gradient-push", "dring4", "-"),
    AuditConfig("gradient-push", "dring4", "fixedk"),
    AuditConfig("gradient-push", "dring4", "qsgd"),
    AuditConfig("gradient-push", "matchings4x2", "fixedk"),
    # partial-participation (edge-fleet simulator) schedules: per-round
    # masked induced subgraphs, q=0.75 participation trace — the sim's
    # round graphs must satisfy the same taint/prng/wire contract
    AuditConfig("sdm-dsgd", "subring4x3", "fixedk_packed"),
    AuditConfig("sdm-dsgd", "subring4x3", "bernoulli"),
    AuditConfig("dsgd", "subring4x3", "-"),
    AuditConfig("gradient-push", "subdring4x3", "fixedk"),
    # negative controls: the analyzer MUST flag these
    AuditConfig("allreduce", "ring4", "-", expect_taint=True),
    AuditConfig("sdm-dsgd", "ring4", "fixedk_packed", sigma=0.0,
                expect_taint=True),
)

#: the quick subset for smoke runs (--quick)
QUICK_IDS = frozenset({
    "sdm-dsgd/ring4/fixedk_packed/sigma1",
    "sdm-dsgd/ring4/qsgd:4/sigma1",
    "sdm-dsgd/matchings4x2/fixedk_packed/sigma1",
    "dsgd/ring4/-/sigma1",
    "gradient-push/dring4/fixedk/sigma1",
    "sdm-dsgd/subring4x3/fixedk_packed/sigma1",
    "sdm-dsgd/ring4/fixedk_packed+ov/sigma1",
    "allreduce/ring4/-/dirty",
})


def parse_topo(spec: str) -> gossip.ScheduleSequence:
    if spec == "ring4":
        return gossip.ensure_sequence(
            gossip.schedule_from_topology(topology.ring(N_NODES)))
    if spec == "dring4":
        return gossip.ensure_sequence(gossip.schedule_from_topology(
            topology.directed_ring(N_NODES)))
    if spec == "matchings4x2":
        return gossip.sequence_from_topologies(
            topology.random_matchings(N_NODES, 2, seed=0), name=spec)
    if spec in ("subring4x3", "subdring4x3"):
        # the edge-fleet simulator's partial-participation schedule: a
        # q=0.75 Bernoulli participation trace (the sim's own fleet PRNG,
        # so the audited graphs are exactly what a sim run compiles)
        # masking the base ring / directed ring per round
        from repro.sim.fleet import Fleet

        base = (topology.directed_ring(N_NODES) if spec == "subdring4x3"
                else topology.ring(N_NODES))
        fleet = Fleet(N_NODES, "q=0.75", seed=0)
        sets = [np.nonzero(fleet.sample_participants())[0]
                for _ in range(3)]
        return gossip.sequence_from_active_sets(base, sets, name=spec)
    raise ValueError(f"unknown audit topology {spec!r}")


def make_cfg(ac: AuditConfig, meth):
    if meth.config_cls is sdm_dsgd.SDMConfig:
        kw = dict(p=0.25, theta=0.15, gamma=0.2, sigma=ac.sigma,
                  clip_c=1.0, overlap=ac.overlap)
        if ac.mode.split(":")[0] in ("qsgd", "qsgdf"):
            return meth.coerce_config(
                sdm_dsgd.SDMConfig(compressor=ac.mode, **kw))
        return meth.coerce_config(sdm_dsgd.SDMConfig(mode=ac.mode, **kw))
    if meth.config_cls is gradient_push.GradientPushConfig:
        return gradient_push.GradientPushConfig(
            gamma=0.2, sigma=ac.sigma, clip_c=1.0,
            compressor=None if ac.mode == "-" else ac.mode, p=0.25,
            overlap=ac.overlap)
    return baselines.DSGDConfig(gamma=0.2, sigma=ac.sigma, clip_c=1.0)


def expected_permutes(meth_name: str, mode: str, seq) -> int:
    """Collective-permutes per compiled step on the plane transport.

    R schedule rounds x wire leaves per payload (1 for dense/packed, 2
    for compressor payloads: values + scale|indices), + R for the
    push-sum mass scalar. Leaf-count-INDEPENDENT: this is the PR-5
    tentpole, now the analyzer's canonical contract (the parity sweep
    imports this).
    """
    r = seq.schedules[0].n_rounds
    base_mode = mode.split(":")[0]
    if mode == "-":
        leaves = 0 if meth_name == "allreduce" else 1
    elif base_mode in ("qsgd", "fixedk", "block"):
        # exchange_payload pytrees: values + scale (qsgd) / indices
        leaves = 2 if (meth_name == "gradient-push"
                       or base_mode == "qsgd") else 1
    else:
        # includes "qsgdf": the fused single-buffer format embeds the
        # norm in the byte payload, so ONE leaf — half of qsgd's wire
        leaves = 1
    extra = r if meth_name == "gradient-push" else 0
    return r * leaves + extra


def allowed_draw_shapes(per_node) -> frozenset:
    """Canonical (rows, lane) shapes mask/noise draws may use: the wire
    plane spec per bucket, plus the fused kernel's LANE-padded plane.
    Anything 2-D on a known lane but taller is kernel-tile padding — the
    PR-1 bug class."""
    spec = plane_mod.ParamPlane.for_tree(per_node)
    shapes = set(spec.plane_shapes())
    total = spec.total_size
    shapes.add((-(-total // KERNEL_LANE), KERNEL_LANE))
    return frozenset(shapes)


def _build(ac: AuditConfig):
    """Trace + compile ``ac``'s distributed train step (never executed)."""
    meth = method_mod.get(ac.method)
    seq = parse_topo(ac.topo)
    n = seq.n_nodes
    cfg = make_cfg(ac, meth)

    rng = np.random.default_rng(0)
    a_stack = jnp.asarray(rng.normal(size=(n, BATCH, DIM)) / 4.0, jnp.float32)
    b_stack = jnp.asarray(rng.normal(size=(n, BATCH)), jnp.float32)
    params0 = jnp.asarray(rng.normal(size=(DIM,)) * 0.1, jnp.float32)
    params_stack = {"w": jnp.broadcast_to(params0, (n, DIM))}
    base_key = jax.random.PRNGKey(42)

    mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
    ex = meth.make_distributed(seq, cfg, "data")

    def dist_train(params_stack, a_st, b_st):
        def inner(p, a, b):
            p = jax.tree.map(lambda v: jnp.squeeze(v, 0), p)
            a, b = jnp.squeeze(a, 0), jnp.squeeze(b, 0)
            me = jax.lax.axis_index("data")
            state = ex.init(p, me)

            def grads_at(tree):
                r = a @ tree["w"] - b
                return {"w": a.T @ r / a.shape[0]}, jnp.mean(r * r)

            def body(state, _):
                state, aux = ex.step(state, grads_at, base_key=base_key)
                return state, aux

            state, losses = jax.lax.scan(body, state, None, length=STEPS)
            # the metric release every real train step performs
            loss = jax.lax.pmean(
                tagging.declared_release(losses[-1], label="loss"), "data")
            return jax.tree.map(lambda v: v[None], state.x), loss[None]

        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P("data"), P("data"), P("data")),
                             out_specs=(P("data"), P("data")),
                             axis_names={"data"},
                             check_vma=False)(params_stack, a_st, b_st)

    args = (params_stack, a_stack, b_stack)
    jaxpr = jax.make_jaxpr(dist_train)(*args)
    hlo = jax.jit(dist_train).lower(*args).compile().as_text()
    per_node = jax.tree.map(lambda v: v[0], params_stack)
    return meth, seq, cfg, jaxpr, hlo, per_node


def _exact_bits(meth, meth_name: str, mode: str, cfg, per_node, seq
                ) -> Optional[int]:
    """Static accounting where it equals the HLO payload bits EXACTLY.

    Deterministic wire formats only: fixed-k / rows / qsgd ship a known
    payload every round. Bernoulli's accounting is the EXPECTED p*d
    (paper convention) while the wire carries the dense masked plane, so
    equality is structurally impossible there (checked by payload shape
    instead). Mass-scalar bits for push-sum ride the same accounting.
    """
    base = mode.split(":")[0]
    if meth_name.startswith("sdm-dsgd") or meth_name == "dc-dsgd":
        if base in ("fixedk_packed", "fixedk_rows", "qsgd", "qsgdf"):
            return int(sdm_dsgd.transmitted_bits_per_step(
                per_node, cfg, seq=seq))
        return None
    if meth_name == "dsgd":
        return int(method_mod.transmitted_bits(meth, per_node, cfg, seq=seq))
    return None


def _wire_findings(ac: AuditConfig, meth, seq, cfg, hlo, per_node) -> List:
    findings: List[dict] = []
    payloads = hlo_analysis.permute_payloads(hlo)
    cperm = hlo_analysis.collective_permute_count(hlo)
    # async overlap lowering must keep start/done pairs balanced — an
    # unmatched start is a permute whose result is never consumed
    for kind, pair in hlo_analysis.async_collective_pairs(hlo).items():
        if pair["start"] != pair["done"]:
            findings.append({"kind": "async-pair-imbalance", "op": kind,
                             "got": pair})
    spec = plane_mod.ParamPlane.for_tree(per_node)
    (p_rows, p_lane), = spec.plane_shapes()
    plane_elems = p_rows * p_lane

    if seq.length == 1:
        exp = expected_permutes(ac.method, ac.mode, seq)
        if cperm != exp:
            findings.append({"kind": "permute-count", "got": cperm,
                             "expected": exp})
        exact = _exact_bits(meth, ac.method, ac.mode, cfg, per_node, seq)
        if exact is not None:
            hlo_bits = sum(pl["bits"] for pl in payloads)
            if hlo_bits != exact:
                findings.append({"kind": "payload-bits", "got": hlo_bits,
                                 "expected": exact})
        if ac.mode == "bernoulli":
            # dense masked plane: every payload permute ships the full
            # plane, one per round
            dense = [pl for pl in payloads
                     if pl["elems"].get("f32", 0) == plane_elems]
            r = seq.schedules[0].n_rounds
            if len(dense) != r:
                findings.append({"kind": "dense-payload-rounds",
                                 "got": len(dense), "expected": r})
    else:
        # replica transport: branch-free payload over every union round
        useq = gossip.union_schedule(seq)
        base = ac.mode.split(":")[0]
        if base == "qsgd":
            pperms = sum(1 for pl in payloads
                         if pl["bits"] >= plane_elems * 8)
        elif ac.mode == "bernoulli":
            pperms = sum(1 for pl in payloads
                         if pl["elems"].get("f32", 0) == plane_elems)
        elif ac.mode == "-":
            pperms = sum(1 for pl in payloads
                         if pl["elems"].get("f32", 0) == plane_elems)
        else:
            from repro.core import sparsifier
            k = sparsifier.num_kept(plane_elems, 0.25)
            pperms = sum(1 for pl in payloads
                         if pl["elems"].get("f32", 0) == k)
        if ac.method == "dsgd":
            # dense full-state exchange lowers to a lax.switch over the
            # L per-round branches (only the live round executes), so the
            # compiled graph carries EVERY branch's permutes — unlike the
            # branch-free union replica transport of the masked payloads.
            expected = sum(s.n_rounds for s in seq.schedules)
        else:
            expected = useq.n_replicas
        if pperms != expected:
            findings.append({"kind": "union-payload-rounds", "got": pperms,
                             "expected": expected})
    return findings


def _compressor_for(meth, cfg) -> Optional[compressor_mod.Compressor]:
    if meth.config_cls is sdm_dsgd.SDMConfig:
        return sdm_dsgd.compressor_of(cfg)
    if meth.config_cls is gradient_push.GradientPushConfig:
        return cfg.make_compressor()
    return None


def accountant_view(ac: AuditConfig, meth, cfg, per_node) -> dict:
    """The privacy constants the RDP accountant charges for this config
    — the certificate column the jaxpr-extracted constants are checked
    against (the other direction lives in ``analyze_calibration``)."""
    d_total = sum(int(np.prod(v.shape))
                  for v in jax.tree.leaves(per_node))
    clip_c = float(getattr(cfg, "clip_c", 0.0) or 0.0) or None
    G = clipping.sensitivity_G(clip_c, d_total) if clip_c else None
    comp = _compressor_for(meth, cfg)
    p_rel = comp.release_probability if comp is not None else 1.0
    view = {
        "sigma": ac.sigma,
        "clip_c": clip_c,
        "d": d_total,
        "G": G,
        "release_p": list(p_rel) if isinstance(p_rel, tuple) else p_rel,
        "sigma_times_c": (ac.sigma * clip_c) if clip_c else None,
        "compressor": comp.name if comp is not None else None,
        "coord_inflation_at_c":
            comp.coord_sensitivity_transfer(clip_c, (DIM,))
            if (comp is not None and clip_c) else None,
    }
    if ac.sigma > 0.0 and clip_c:
        try:
            params = privacy.PrivacyParams(
                G=G, m=BATCH, tau=1.0 / BATCH, p=p_rel, sigma=ac.sigma)
            view["epsilon_at_T"] = privacy.epsilon_sdm(
                params, STEPS, eps_target=0.5)
        except ValueError:
            view["epsilon_at_T"] = None
    return view


def _range_certificate(ac: AuditConfig, meth, cfg, hlo, per_node
                       ) -> Tuple[List[dict], Optional[dict]]:
    """Integer-range pass: only quantized wire formats have integer
    planes to certify; everything else is trivially in-range f32."""
    comp = _compressor_for(meth, cfg)
    if not isinstance(comp, compressor_mod.QSGDCompressor):
        return [], None
    spec = plane_mod.ParamPlane.for_tree(per_node)
    (p_rows, p_lane), = spec.plane_shapes()
    fused = isinstance(comp, compressor_mod.FusedQSGDCompressor)
    cert = sensitivity.qsgd_range_certificate(
        comp.bits, fused=fused, plane_elems=p_rows * p_lane)
    findings = list(cert.pop("findings"))
    # the proved wire dtype must actually appear in the HLO permute
    # payloads — a silent widening to f32 would void the range proof.
    payloads = hlo_analysis.permute_payloads(hlo)
    if not any(pl["elems"].get(cert["wire_dtype"]) for pl in payloads):
        findings.append({
            "kind": "wire-dtype-missing", "dtype": cert["wire_dtype"],
            "detail": "no collective-permute payload ships the certified "
                      "integer dtype"})
    return findings, cert


def audit_config(ac: AuditConfig, passes=PASSES) -> dict:
    """Run the selected audit passes on one configuration.

    ``passes`` (an iterable of ``PASSES`` names) lets CI shards and
    local debugging run one pass without the rest; the report row always
    carries every key, with unselected passes empty and their
    certificate fields ``None``.
    """
    passes = frozenset(passes)
    meth, seq, cfg, jaxpr, hlo, per_node = _build(ac)
    source_labels = {1: "data", 2: "data"}

    taint = jaxpr_taint.analyze_taint(jaxpr, source_labels) \
        if "taint" in passes else None
    prng = prng_lint.analyze_prng(
        jaxpr, allowed_shapes=allowed_draw_shapes(per_node)) \
        if "prng" in passes else None
    wire = _wire_findings(ac, meth, seq, cfg, hlo, per_node) \
        if "wire" in passes else []

    # negative-control configs get certificates but no certifier gates:
    # their whole point is that the QUALITATIVE pass flags them.
    claims = (not ac.expect_taint) and ac.sigma > 0.0
    clip_c = float(getattr(cfg, "clip_c", 0.0) or 0.0) or None
    sens = sensitivity.analyze_sensitivity(
        jaxpr, source_labels, clip_c=clip_c, check=claims) \
        if "sensitivity" in passes else None
    calib = calibration.analyze_calibration(
        jaxpr, expected_sigma=ac.sigma, expected_clip=clip_c,
        check=claims) if "calibration" in passes else None
    rng_findings, rng_cert = _range_certificate(
        ac, meth, cfg, hlo, per_node) if "range" in passes else ([], None)
    ovl = calibration.analyze_overlap(
        jaxpr, overlap=ac.overlap,
        needs_replicas=gossip.needs_replicas(seq)) \
        if "overlap" in passes else None

    taint_findings = list(taint["findings"]) if taint else []
    if taint and ac.expect_taint:
        if taint_findings:
            taint_findings = []     # expected dirt, analyzer has teeth
        else:
            taint_findings = [{"kind": "expected-taint-missing",
                               "detail": "known-non-private config produced "
                                         "no taint finding"}]
    sens_findings = sens["findings"] if sens else []
    calib_findings = calib["findings"] if calib else []
    ovl_findings = ovl["findings"] if ovl else []
    prng_findings = prng["findings"] if prng else []
    violations = (taint_findings + prng_findings + wire + sens_findings
                  + calib_findings + rng_findings + ovl_findings)
    certificate = {
        "accountant": accountant_view(ac, meth, cfg, per_node),
        "sanitize_bounds": sens["sanitize_sites"] if sens else None,
        "wire_coord_bound": sens["wire_coord_bound"] if sens else None,
        "clip_sites": sens["clip_sites"] if sens else None,
        "extracted_noise": calib["sanitize_sites"] if calib else None,
        "integer_ranges": rng_cert,
        "overlap": ({"verdict": ovl["verdict"],
                     "n_pending": ovl["n_pending"]} if ovl else None),
    }
    return {
        "id": ac.id,
        "expect_taint": ac.expect_taint,
        "passes": sorted(passes & set(PASSES)),
        "taint": taint_findings,
        "prng": prng_findings,
        "wire": wire,
        "sensitivity": sens_findings,
        "calibration": calib_findings,
        "range": rng_findings,
        "overlap": ovl_findings,
        "certificate": certificate,
        "releases": taint["releases"] if taint else [],
        "n_draws": prng["n_draws"] if prng else 0,
        "n_sanitize_sites": taint["n_sanitize_sites"] if taint else 0,
        "status": "fail" if violations else "pass",
    }
