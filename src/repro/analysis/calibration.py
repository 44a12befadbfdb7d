"""Noise-calibration cross-check + overlap double-buffer hazard pass.

Calibration
-----------
The accountant (``core.privacy``) charges epsilon for a Gaussian mask of
std sigma; ``masked_grad`` is SUPPOSED to add exactly that sigma. A
miscalibrated wiring — sigma_for_budget computed for one batch size and
applied at another, a stray scale factor on the noise — keeps every
test green and silently reports a wrong epsilon. This pass extracts the
CONCRETE noise std from the compiled jaxpr at each ``sanitize`` site
and cross-checks it against the sigma the config's accountant charges.

Extraction rides jax's own lowering of ``jax.random.normal``: uniform
bits -> ``erf_inv`` -> ``* sqrt(2)`` -> ``* sigma``. The abstract value
is the SET of Gaussian stds a value carries: ``erf_inv`` output is a
std-``1/sqrt(2)`` Gaussian (of U(-1,1) input), scalar-literal muls
scale every std in the set, adds/structural ops union, and any other op
clears (a squared Gaussian is not a Gaussian). At a ``sanitize`` site
the operand is clipped-data + noise, so its std set must contain the
accountant's sigma.

Overlap hazards
---------------
``cfg.overlap`` double-buffers the wire planes: the fresh exchange
result (tagged ``pending_buffer``) must ride the scan carry UNTOUCHED
and be consumed exactly one round later — one-step staleness, the
delayed-mixing semantics the dense oracle pins. This pass proves that
ordering statically with a token-propagation walk over each training
scan body:

* ``pending-not-carried``      — the tagged buffer never reaches a
  carry slot (the exchange result is dropped or consumed same-round);
* ``pending-same-round-read``  — the fresh buffer leaks into a scan
  output or a SECOND carry slot (same-round read: staleness 0);
* ``pending-self-dependence``  — the new pending buffer depends on the
  old one (staleness would exceed one round);
* ``pending-dropped``          — last round's buffer is never consumed;
* ``overlap-untagged``         — an overlap config whose jaxpr shows no
  pending tag at all (the double buffer got optimized out or bypassed);
* ``overlap-replica-schedule`` — overlap on a replica (time-varying)
  schedule, rejected statically instead of at trace time.
"""
from __future__ import annotations

import math
from typing import Dict, FrozenSet, List, Optional, Tuple

from repro.analysis import jaxpr_walk
from repro.core import tagging

__all__ = ["analyze_calibration", "analyze_overlap", "GAUSS_ERF_INV_STD"]

#: std of erf_inv(U(-1, 1)): jax's normal is erf_inv(u) * sqrt(2).
GAUSS_ERF_INV_STD = 1.0 / math.sqrt(2.0)

# ops through which "this value contains a Gaussian of std s" survives:
# adds (independent offsets), layout ops, dtype casts, data movement.
_UNION_PRIMS = frozenset({
    "add", "sub", "neg", "broadcast_in_dim", "reshape", "transpose",
    "squeeze", "expand_dims", "slice", "concatenate", "pad", "rev",
    "convert_element_type", "reduce_precision", "copy", "gather",
    "dynamic_slice", "dynamic_update_slice", "select_n",
    "optimization_barrier", "stop_gradient",
})

_CONTROL = frozenset({"scan", "while", "cond", "switch", "pallas_call"})

Stds = FrozenSet[float]


def _round_std(v: float) -> float:
    return float(f"{v:.12g}")


def _literal_scalar(var) -> Optional[float]:
    if not jaxpr_walk._is_literal(var):
        return None
    val = var.val
    try:
        if hasattr(val, "shape") and val.shape not in ((), (1,)):
            return None
        return float(val.item() if hasattr(val, "item") else val)
    except Exception:
        return None


class _NoiseInterp(jaxpr_walk.JaxprInterpreter):
    def __init__(self):
        self.sanitize_sites: Dict[tuple, dict] = {}
        self.clip_sites: Dict[tuple, dict] = {}

    def bottom(self) -> Stds:
        return frozenset()

    def join(self, a: Stds, b: Stds) -> Stds:
        return a | b

    def on_eqn(self, eqn, in_vals, ctx, def_prim):
        name = eqn.primitive.name
        if name == "erf_inv":
            return [frozenset({_round_std(GAUSS_ERF_INV_STD)})]
        if name == tagging.SANITIZE:
            key = (id(eqn), ctx.path, ctx.branch)
            rec = self.sanitize_sites.setdefault(
                key, {"site": jaxpr_walk.format_site(eqn),
                      "stds": frozenset()})
            rec["stds"] = rec["stds"] | in_vals[0]
            return [frozenset()]
        if name == tagging.CLIP:
            key = (id(eqn), ctx.path, ctx.branch)
            self.clip_sites.setdefault(
                key, {"site": jaxpr_walk.format_site(eqn),
                      "bound": float(eqn.params.get("bound", float("nan")))})
            return [in_vals[0]]
        if name in tagging.TAG_PRIMITIVES:
            return [in_vals[0]]
        if name in ("mul", "div"):
            lit0 = _literal_scalar(eqn.invars[0])
            lit1 = _literal_scalar(eqn.invars[1])
            if name == "mul" and lit0 is not None:
                return [frozenset(_round_std(s * abs(lit0))
                                  for s in in_vals[1])]
            if lit1 is not None and lit1 != 0.0:
                c = abs(lit1) if name == "mul" else 1.0 / abs(lit1)
                return [frozenset(_round_std(s * c) for s in in_vals[0])]
            return [frozenset()]
        if name in _UNION_PRIMS:
            return None   # default join-of-inputs = union
        if name in _CONTROL or name in jaxpr_walk._ALIGNED_CALLS:
            return None   # boundary recursion
        if any(hasattr(v, "eqns") or hasattr(v, "jaxpr")
               for v in eqn.params.values()):
            return None
        # any other op destroys Gaussian-ness (squares, norms, compares).
        return [frozenset() for _ in eqn.outvars]


def analyze_calibration(closed_jaxpr, *, expected_sigma: float,
                        expected_clip: float | None,
                        check: bool = True, rel_tol: float = 1e-4) -> dict:
    """Extract per-``sanitize``-site noise stds and cross-check them
    against the accountant's sigma (and the declared clip against the
    config's C). ``check=False`` still returns the extracted constants
    for the certificate."""
    interp = _NoiseInterp()
    jaxpr, _ = jaxpr_walk._unpack(closed_jaxpr)
    interp.run(closed_jaxpr, [frozenset()] * len(jaxpr.invars))

    findings: List[dict] = []
    sites = []
    for rec in interp.sanitize_sites.values():
        stds = sorted(rec["stds"])
        matched = [s for s in stds
                   if math.isclose(s, expected_sigma, rel_tol=rel_tol)]
        sites.append({"site": rec["site"], "stds": stds,
                      "extracted_sigma": matched[0] if matched
                      else (stds[-1] if stds else None)})
        if not check:
            continue
        if not stds:
            findings.append({
                "kind": "noise-scale-unextracted", "site": rec["site"],
                "detail": "sanitize operand carries no recognizable "
                          "Gaussian noise term"})
        elif not matched:
            findings.append({
                "kind": "noise-scale-mismatch", "site": rec["site"],
                "jaxpr_sigma": stds, "accountant_sigma": expected_sigma})
    if check and expected_sigma > 0.0 and not interp.sanitize_sites:
        findings.append({
            "kind": "missing-noise",
            "detail": f"config charges sigma={expected_sigma} but the "
                      "jaxpr has no sanitize site"})
    # clip-bound cross-checking lives in the sensitivity pass (it owns
    # the bound domain); the sites are recorded here only for the cert.
    del expected_clip
    clip_rows = [{"site": rec["site"], "bound": rec["bound"]}
                 for rec in interp.clip_sites.values()]
    return {"findings": findings, "sanitize_sites": sites,
            "clip_sites": clip_rows}


# ==========================================================================
# Overlap double-buffer hazards (token propagation over scan bodies).
# ==========================================================================

class _TokenInterp(jaxpr_walk.JaxprInterpreter):
    """Propagates frozensets of provenance tokens; ``pending_buffer``
    tags mint a fresh token in addition to passing their inputs."""

    def __init__(self):
        self.pending: List[Tuple[tuple, str]] = []   # (token, site)
        self._uids: Dict[tuple, tuple] = {}

    def bottom(self) -> frozenset:
        return frozenset()

    def join(self, a, b):
        return a | b

    def on_eqn(self, eqn, in_vals, ctx, def_prim):
        if eqn.primitive.name == tagging.PENDING:
            key = (id(eqn), ctx.path, ctx.branch)
            tok = self._uids.get(key)
            if tok is None:
                tok = ("pend", len(self._uids))
                self._uids[key] = tok
                self.pending.append((tok, jaxpr_walk.format_site(eqn)))
            return [in_vals[0] | {tok}]
        return None


def _iter_scans(jaxpr, consts):
    """Yield every (scan eqn, body jaxpr, body consts) anywhere in the
    program (train loops live under jit/shard_map)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            sub, sub_consts = jaxpr_walk._unpack(eqn.params["jaxpr"])
            yield eqn, sub, sub_consts
            yield from _iter_scans(sub, sub_consts)
            continue
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                sub, sub_consts = jaxpr_walk._unpack(v)
                yield from _iter_scans(sub, sub_consts)
        if name in ("cond", "switch"):
            for br in eqn.params.get("branches", ()):
                sub, sub_consts = jaxpr_walk._unpack(br)
                yield from _iter_scans(sub, sub_consts)


def analyze_overlap(closed_jaxpr, *, overlap: bool,
                    needs_replicas: bool = False) -> dict:
    """Statically verify the overlap double-buffer discipline (see
    module docstring). Non-overlap configs verify vacuously (verdict
    ``n/a``) but still reject stray pending tags."""
    findings: List[dict] = []
    if overlap and needs_replicas:
        findings.append({
            "kind": "overlap-replica-schedule",
            "detail": "overlap=True requires a static (non-replica) "
                      "schedule; replica delivery would consume the "
                      "pending buffer at unbounded staleness"})
    jaxpr, consts = jaxpr_walk._unpack(closed_jaxpr)
    n_pending = 0
    loops = []
    for eqn, sub, sub_consts in _iter_scans(jaxpr, consts):
        interp = _TokenInterp()
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        n_xs = len(sub.invars) - nc - ncar
        carry_in = [frozenset({("carry", j)}) for j in range(ncar)]
        seed = [frozenset()] * nc + carry_in + [frozenset()] * n_xs
        # ONE body evaluation, not a fixpoint: the hazard question is
        # about the single-iteration dataflow new_carry = f(old_carry).
        ctx = jaxpr_walk.Ctx(loop_depth=1, path=(id(eqn),))
        outs = interp._eval(sub, sub_consts, seed, ctx)
        carry_out, ys = outs[:ncar], outs[ncar:]
        if not interp.pending:
            continue
        n_pending += len(interp.pending)
        for tok, site in interp.pending:
            slots = [j for j, c in enumerate(carry_out) if tok in c]
            if not slots:
                findings.append({"kind": "pending-not-carried",
                                 "site": site})
            if any(tok in y for y in ys) or len(slots) > 1:
                findings.append({
                    "kind": "pending-same-round-read", "site": site,
                    "detail": "fresh exchange result read in the round "
                              "that produced it (staleness 0, not 1)"})
            for j in slots:
                if ("carry", j) in carry_out[j]:
                    findings.append({
                        "kind": "pending-self-dependence", "site": site,
                        "detail": "new pending buffer depends on the "
                                  "old one: staleness exceeds one round"})
                consumed = any(("carry", j) in out
                               for k, out in enumerate(outs) if k != j)
                if not consumed:
                    findings.append({
                        "kind": "pending-dropped", "site": site,
                        "detail": "last round's pending buffer is never "
                                  "consumed by the update"})
            loops.append({"site": site, "carry_slots": slots})
    if overlap and n_pending == 0:
        findings.append({
            "kind": "overlap-untagged",
            "detail": "overlap config but no pending_buffer tag in any "
                      "training scan (double buffer bypassed?)"})
    if not overlap and n_pending > 0:
        findings.append({
            "kind": "pending-without-overlap",
            "detail": "pending_buffer tag in a non-overlap config"})
    verdict = "n/a" if not overlap else (
        "ok" if not findings else "hazard")
    return {"findings": findings, "verdict": verdict,
            "n_pending": n_pending, "buffers": loops}
