"""Shared abstract-interpretation machinery over (Closed)Jaxprs.

``JaxprInterpreter`` walks a jaxpr and recurses through every call
boundary jax emits on this toolchain — ``jit``, ``closed_call``,
``scan`` (to carry fixpoint), ``while``, ``cond``/``switch`` branches,
``shard_map``, ``custom_jvp/vjp_call`` and ``remat`` — propagating one
abstract value per jaxpr variable. Subclasses define the lattice
(``bottom``/``join``), per-primitive transfer functions (``rules``),
and may observe every equation (``on_eqn``) to record findings.

The walk is context-aware: ``Ctx`` carries the enclosing scan depth
(loops that actually iterate, ``length > 1``) and a branch path of
``(cond_eqn_uid, branch_index)`` pairs so clients can tell apart two
events that are mutually exclusive (different branches of one
``lax.switch``) from two events on one execution path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

__all__ = ["Ctx", "JaxprInterpreter", "format_site"]


def _is_literal(v) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


def _unpack(j) -> Tuple[Any, Sequence[Any]]:
    """Jaxpr | ClosedJaxpr -> (open jaxpr, consts)."""
    if hasattr(j, "jaxpr"):
        return j.jaxpr, j.consts
    return j, ()


def format_site(eqn) -> str:
    """Best-effort user-frame 'file:line' for a finding."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        pass
    return "?"


@dataclasses.dataclass(frozen=True)
class Ctx:
    """Where in the program the interpreter currently is."""

    loop_depth: int = 0                       # enclosing scans with length>1
    branch: Tuple[Tuple[int, int], ...] = ()  # (cond_uid, branch_idx) path
    path: Tuple[int, ...] = ()                # enclosing call-eqn uids

    def in_loop(self) -> bool:
        return self.loop_depth > 0


# call-like primitives with a single positionally-aligned subjaxpr
_ALIGNED_CALLS = {
    "jit", "closed_call", "remat2", "checkpoint", "remat",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call_jaxpr", "shard_map", "custom_partitioning",
}
_SUB_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")

_MAX_FIXPOINT = 32


class JaxprInterpreter:
    """Abstract interpreter base; subclass and override the hooks."""

    # ---- lattice ---------------------------------------------------------
    def bottom(self):
        raise NotImplementedError

    def join(self, a, b):
        raise NotImplementedError

    def literal(self, lit, ctx: Ctx):
        return self.bottom()

    def const(self, val, ctx: Ctx):
        return self.bottom()

    # ---- transfer --------------------------------------------------------
    def on_eqn(self, eqn, in_vals, ctx: Ctx, def_prim: Dict) -> "List | None":
        """Observe/replace an equation. Return out_vals to OVERRIDE the
        default transfer, or None to fall through (boundary handling or
        the default join-of-inputs rule)."""
        return None

    def default_out(self, eqn, in_vals, ctx: Ctx) -> List:
        joined = self.bottom()
        for v in in_vals:
            joined = self.join(joined, v)
        return [joined for _ in eqn.outvars]

    def loop_carry_seed(self, val, ctx: Ctx):
        """Abstract value for a loop-carried input as seen by the body
        (hook for marking loop-variance)."""
        return val

    # ---- driver ----------------------------------------------------------
    def run(self, closed_jaxpr, in_vals: Sequence) -> List:
        jaxpr, consts = _unpack(closed_jaxpr)
        ctx = Ctx()
        return self._eval(jaxpr, consts, list(in_vals), ctx)

    def _read(self, env, v, ctx: Ctx):
        if _is_literal(v):
            return self.literal(v, ctx)
        return env.get(v, self.bottom())

    def _eval(self, jaxpr, consts, in_vals: List, ctx: Ctx) -> List:
        env: Dict = {}
        def_prim: Dict = {}
        for var, c in zip(jaxpr.constvars, consts):
            env[var] = self.const(c, ctx)
        n = min(len(jaxpr.invars), len(in_vals))
        # tail-align: extra leading operands (e.g. custom_vjp consts) get
        # dropped; missing ones default to bottom.
        for var, val in zip(jaxpr.invars[-n:] if n else [], in_vals[-n:]):
            env[var] = val
        for var in jaxpr.invars[:len(jaxpr.invars) - n]:
            env.setdefault(var, self.bottom())
        for eqn in jaxpr.eqns:
            in_vals_e = [self._read(env, v, ctx) for v in eqn.invars]
            outs = self.on_eqn(eqn, in_vals_e, ctx, def_prim)
            if outs is None:
                outs = self._eval_eqn(eqn, in_vals_e, ctx)
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
                def_prim[var] = eqn.primitive.name
        return [self._read(env, v, ctx) for v in jaxpr.outvars]

    # ---- boundaries ------------------------------------------------------
    def _eval_eqn(self, eqn, in_vals: List, ctx: Ctx) -> List:
        name = eqn.primitive.name
        params = eqn.params
        if name == "scan":
            return self._eval_scan(eqn, in_vals, ctx)
        if name == "while":
            return self._eval_while(eqn, in_vals, ctx)
        if name in ("cond", "switch"):
            return self._eval_cond(eqn, in_vals, ctx)
        if name == "pallas_call":
            return self._eval_pallas(eqn, in_vals, ctx)
        if name in _ALIGNED_CALLS:
            for key in _SUB_KEYS:
                if key in params:
                    sub, consts = _unpack(params[key])
                    sub_ctx = dataclasses.replace(
                        ctx, path=ctx.path + (id(eqn),))
                    outs = self._eval(sub, consts, in_vals, sub_ctx)
                    return self._fit(outs, len(eqn.outvars), in_vals)
        # unknown primitive carrying subjaxprs: conservative recursion
        subs = [v for v in params.values()
                if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        if subs:
            joined_in = self.bottom()
            for v in in_vals:
                joined_in = self.join(joined_in, v)
            acc = joined_in
            for s in subs:
                sub, consts = _unpack(s)
                for o in self._eval(sub, consts,
                                    [joined_in] * len(sub.invars), ctx):
                    acc = self.join(acc, o)
            return [acc for _ in eqn.outvars]
        return self.default_out(eqn, in_vals, ctx)

    def _fit(self, outs: List, n: int, in_vals: List) -> List:
        if len(outs) == n:
            return outs
        joined = self.bottom()
        for v in list(outs) + list(in_vals):
            joined = self.join(joined, v)
        return [joined for _ in range(n)]

    def _eval_pallas(self, eqn, in_vals: List, ctx: Ctx) -> List:
        """Recurse into a pallas kernel body with HEAD-aligned refs.

        The kernel jaxpr's invars are ``[in_refs..., out_refs...]`` Ref
        avals — the eqn's operands map onto the FIRST invars and the
        remaining out-refs seed at bottom (generic tail-alignment would
        mis-map operands onto out-refs). The kernel reads/writes refs via
        ``get``/``swap``, which the default join-of-inputs transfer
        already propagates through, so key identity and taint survive
        into the kernel body. Kernel outputs are whatever the out-refs
        can't tell us here, so the eqn outputs conservatively join the
        kernel's formal outputs (usually none) with the eqn operands.
        """
        sub, consts = _unpack(eqn.params["jaxpr"])
        sub_ctx = dataclasses.replace(ctx, path=ctx.path + (id(eqn),))
        vals = list(in_vals[:len(sub.invars)])
        vals += [self.bottom()] * (len(sub.invars) - len(vals))
        outs = self._eval(sub, consts, vals, sub_ctx)
        return self._fit(outs, len(eqn.outvars), in_vals)

    def _eval_scan(self, eqn, in_vals: List, ctx: Ctx) -> List:
        params = eqn.params
        sub, consts = _unpack(params["jaxpr"])
        nc = params.get("num_consts", 0)
        ncar = params.get("num_carry", 0)
        length = params.get("length", 2) or 2
        body_ctx = dataclasses.replace(
            ctx, loop_depth=ctx.loop_depth + (1 if length > 1 else 0),
            path=ctx.path + (id(eqn),))
        carry = [self.loop_carry_seed(v, body_ctx)
                 for v in in_vals[nc:nc + ncar]]
        xs = [self.loop_carry_seed(v, body_ctx) for v in in_vals[nc + ncar:]]
        outs: List = []
        for _ in range(_MAX_FIXPOINT):
            outs = self._eval(sub, consts, in_vals[:nc] + carry + xs,
                              body_ctx)
            new_carry = [self.join(a, b) for a, b in zip(carry, outs[:ncar])]
            if all(a == b for a, b in zip(new_carry, carry)):
                break
            carry = new_carry
        return self._fit(outs, len(eqn.outvars), in_vals)

    def _eval_while(self, eqn, in_vals: List, ctx: Ctx) -> List:
        params = eqn.params
        cond_sub, cond_consts = _unpack(params["cond_jaxpr"])
        body_sub, body_consts = _unpack(params["body_jaxpr"])
        cn = params.get("cond_nconsts", 0)
        bn = params.get("body_nconsts", 0)
        body_ctx = dataclasses.replace(ctx, loop_depth=ctx.loop_depth + 1,
                                       path=ctx.path + (id(eqn),))
        carry = [self.loop_carry_seed(v, body_ctx) for v in in_vals[cn + bn:]]
        for _ in range(_MAX_FIXPOINT):
            self._eval(cond_sub, cond_consts, in_vals[:cn] + carry, body_ctx)
            outs = self._eval(body_sub, body_consts,
                              in_vals[cn:cn + bn] + carry, body_ctx)
            new_carry = [self.join(a, b) for a, b in zip(carry, outs)]
            if all(a == b for a, b in zip(new_carry, carry)):
                break
            carry = new_carry
        return self._fit(carry, len(eqn.outvars), in_vals)

    def _eval_cond(self, eqn, in_vals: List, ctx: Ctx) -> List:
        branches = eqn.params["branches"]
        n_out = len(eqn.outvars)
        acc = [self.bottom() for _ in range(n_out)]
        for idx, br in enumerate(branches):
            sub, consts = _unpack(br)
            br_ctx = dataclasses.replace(
                ctx, branch=ctx.branch + ((id(eqn), idx),),
                path=ctx.path + (id(eqn),))
            outs = self._fit(self._eval(sub, consts, in_vals[1:], br_ctx),
                             n_out, in_vals)
            acc = [self.join(a, b) for a, b in zip(acc, outs)]
        return acc


def branch_compatible(a: Tuple[Tuple[int, int], ...],
                      b: Tuple[Tuple[int, int], ...]) -> bool:
    """True unless the two branch paths take DIFFERENT branches of the
    same cond — mutually exclusive events can't co-occur at runtime."""
    da, db = dict(a), dict(b)
    return all(db[uid] == idx for uid, idx in da.items() if uid in db)
