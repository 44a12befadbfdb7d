"""SDM-DSGD (Algorithm 1) — reference simulator and distributed TPU step.

The algorithm, per node i, per iteration t (paper Eq. (3)):

    x_t = x_{t-1} + S(d_{t-1})                 # everyone advances public copies
    y_t = (1-theta) x_t
          + theta * (W~ x_t - gamma (grad f(x_t; batch) + eta)),  eta~N(0, sigma^2 I)
    d_t = y_t - x_t

Each node transmits only S(d_i); neighbours maintain exact replicas of
the *public* copies x_j (they advance them with the received S(d_j)),
so the distributed state per node is:

    x — the node's own public copy (identical to what neighbours hold),
    s — the running weighted neighbour sum  sum_{j in N_i} W_ij x_j,
    d — the differential awaiting transmission next round.

On a STATIC graph the replicas never need to be materialized: with
time-invariant weights the weighted sum folds incrementally
(s += sum_j W_ij S(d_j)), which is what the two/three-buffer state
above exploits. On a genuinely time-varying schedule sequence the
increments must instead land in EXPLICIT per-neighbour replicas
(``SDMState.xhat``, one slot per union-graph round, fed over every
union edge every round so receivers see every increment) and s is
recomputed fresh with the CURRENT round's W(t) — exact W(t)-mixing on
B-connected sequences, at deg_union x model extra state per node.

Two implementations, bit-for-bit testable against each other:

* ``ReferenceSimulator`` — all n nodes stacked on a leading axis on one
  host, gossip by dense einsum with any Topology (used for the paper's
  CPU-scale experiments: MNIST/CIFAR-style models, ER graphs).
* ``distributed_advance`` / ``distributed_commit`` — per-node code to run
  inside `jax.shard_map` with the node axis manual; ring gossip via
  `collective-permute`, optionally packed fixed-k payloads.

Wire-plane transport (PR 5): the whole differential is bucketized into
contiguous ``repro.core.plane`` wire planes and the compressor draw /
index lists / ppermute rounds run ONCE PER PLANE instead of once per pytree
leaf — a compiled distributed step issues exactly R collective-permutes
per exchange regardless of the model's leaf count, and the distributed
state carries ``s`` / ``d`` (and the replica stack ``xhat``) as
plane-shaped f32 buffers. Both executors draw sparsifier/quantizer bits
at PLANE granularity (one draw over the zero-padded (rows, LANE) buffer
per bucket, keyed ``fold_in(base, bucket)``), so trajectories CHANGED at
this PR relative to the per-leaf draws — exactly like the PR-1 break
when mask draws moved to the canonical LANE-padded shape. Reference and
distributed were rewired together, so the parity sweep stays tight.

Baselines (DSGD, DC-DSGD) live in ``baselines.py``; DC-DSGD is exactly
``SDMConfig(theta=1.0, sigma=0.0)`` — the generalization claim.
"""
from __future__ import annotations

import dataclasses
from fractions import Fraction
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import clipping, compressor as compressor_mod, gossip
from repro.core import plane as plane_mod, tagging
from repro.core.topology import Topology

__all__ = ["SDMConfig", "SDMState", "ReferenceSimulator", "masked_grad",
           "init_distributed_state", "distributed_advance",
           "distributed_commit", "compressor_of", "wire_shape_tree",
           "sparsify_planes_stacked",
           "transmitted_elements_per_step", "transmitted_bits_per_step"]

PyTree = Any


@dataclasses.dataclass(frozen=True)
class SDMConfig:
    """Hyper-parameters of Algorithm 1.

    ``compressor``, when set, is a ``repro.core.compressor`` spec that
    SELECTS the wire format by name (the preferred axis):
    'bernoulli' | 'fixedk[:block]' | 'block:<B>' | 'rows' |
    'qsgd[:bits]' | 'qsgdf[:bits]' | any registered family. Either
    spelling is resolved once, at construction, to the Compressor object
    (``wire``, what ``compressor_of`` returns) that owns sensitivity,
    wire-cost accounting and the transport; ``mode`` is then read off
    it ('payload' for every family without a legacy spelling).

    mode (legacy spelling, still accepted):
      'bernoulli'     — paper-faithful i.i.d. Bernoulli(p) masking, dense payloads.
      'fixedk_packed' — seed-synchronized fixed-k packed payloads over flat
                        pack_block-coordinate blocks (TPU adaptation).
      'fixedk_rows'   — packed payloads over trailing-dim rows: keeps the
                        tensor-parallel sharding of every leaf intact
                        (the production choice; see EXPERIMENTS.md §Perf).
      'qsgd'          — QSGD stochastic quantization of the differential
                        (qsgd_bits levels; int8 wire payload via the
                        generic gossip.exchange_payload transport).

    ``p`` may be a per-node tuple (heterogeneous sparsity budgets, e.g.
    degree-weighted): node i then transmits with probability p[i].
    Supported in 'bernoulli' and 'fixedk_packed' modes — fixed-k wire
    payloads pad to the max-k across nodes (zero rows beyond a node's
    own k), so one static ppermute shape serves every budget. The
    privacy accountant uses the worst-case (max-p) node; Lemma-1's theta
    bound the most restrictive (min-p).
    """

    p: "float | Tuple[float, ...]" = 0.2
    theta: float = 0.6
    gamma: float = 0.01
    sigma: float = 0.0
    clip_c: float | None = None
    mode: str = "bernoulli"
    pack_block: int = 1   # fixedk granularity (coords per transmitted block)
    compressor: str | None = None   # compressor spec; overrides mode
    qsgd_bits: int = 8    # quantizer levels (mode='qsgd')
    # BEYOND-PAPER extension (off by default = paper-faithful): carry the
    # unsent compression residual e = d - S(d) into the next round's
    # differential (error feedback a la Stich et al. [20], which the paper
    # cites but does not use). FINDING (tests/test_error_feedback.py): EF
    # requires a contractive compressor, and p-scaling the differential
    # slows the CONSENSUS correction inside d until disagreement outruns
    # it — long-horizon drift. Structural evidence for the paper's
    # unbiasedness requirement; keep off for real training.
    error_feedback: bool = False
    # Overlapped transport (one-step-stale gossip): the exchange issued
    # at step t is NOT waited on inside step t — its weighted neighbour
    # increments land in a pending double buffer (``SDMState.nb``) and
    # are folded into s at step t+1, so the collective-permute can fly
    # under the whole gradient computation instead of serializing with
    # the mixing update. Because d_0 = 0 (S(0) = 0, the same invariant
    # PR 7's withhold/defer staleness machinery relies on), neighbours
    # always mix a one-step-stale but EXACT public copy — a principled,
    # deterministic trajectory change, not a race. overlap=False is
    # byte-identical to the historical step. Static (non-replica)
    # schedules only.
    overlap: bool = False

    # The wire format, resolved ONCE from ``compressor`` or ``mode`` (what
    # ``compressor_of`` returns). Derived from the fields above, so it
    # takes no part in == or hash.
    wire: compressor_mod.Compressor = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def __post_init__(self) -> None:
        if isinstance(self.p, (list, tuple)):
            object.__setattr__(self, "p", tuple(float(v) for v in self.p))
            if not self.p:
                raise ValueError("per-node p must be non-empty")
            if any(not (0.0 < v <= 1.0) for v in self.p):
                raise ValueError("every per-node p must be in (0,1]")
        elif not (0.0 < self.p <= 1.0):
            raise ValueError("p in (0,1]")
        if not (0.0 < self.theta <= 1.0):
            raise ValueError("theta in (0,1]")
        spec = self.compressor or {
            "bernoulli": "bernoulli",
            "fixedk_packed": f"fixedk:{self.pack_block}",
            "fixedk_rows": "rows",
            "qsgd": f"qsgd:{self.qsgd_bits}"}.get(self.mode)
        if spec is None:
            raise ValueError("mode='payload' needs a compressor spec"
                             if self.mode == "payload"
                             else f"unknown mode {self.mode}")
        # through the registry factories, and (mode, pack_block,
        # qsgd_bits) read back off the object, so per-family defaults
        # cannot drift from compressor.make
        wire = compressor_mod.make(spec, p=self.p)
        object.__setattr__(self, "wire", wire)
        object.__setattr__(self, "mode", wire.sdm_mode)
        object.__setattr__(self, "pack_block",
                           getattr(wire, "block", self.pack_block))
        object.__setattr__(self, "qsgd_bits",
                           getattr(wire, "bits", self.qsgd_bits))
        if self.error_feedback and wire.transport == "payload":
            # EF undoes the sparsifiers' 1/p amplification by scaling the
            # transmitted update by p; quantizers/generic payloads have
            # no such factor, so the scale would silently discard (1-p)
            # of every update.
            raise ValueError("error_feedback is a sparsifier-path "
                             f"extension; unsupported with mode={self.mode!r}")
        if isinstance(self.p, tuple):
            if wire.transport not in ("dense", "blocks"):
                raise ValueError(
                    "heterogeneous per-node p needs mode='bernoulli' or "
                    "'fixedk_packed' (pad-to-max-k payloads); "
                    f"got mode={self.mode!r}")
            if self.error_feedback:
                raise ValueError(
                    "error_feedback with per-node p is unsupported")

    @property
    def p_min(self) -> float:
        """Most restrictive (sparsest) node's p — drives Lemma-1 bounds."""
        return min(self.p) if isinstance(self.p, tuple) else self.p

    @property
    def p_max(self) -> float:
        """Worst-case (densest) node's p — drives the privacy accountant."""
        return max(self.p) if isinstance(self.p, tuple) else self.p

    def p_of(self, node):
        """Node's transmit probability: the scalar, or p[node] (traceable)."""
        if isinstance(self.p, tuple):
            return jnp.asarray(self.p, jnp.float32)[node]
        return self.p

    def validate_against(self, topo: Topology, L: float = 1.0) -> None:
        """Assert Lemma 1's theta < 2p/(1 - lambda_n + gamma L).

        With per-node p the bound must hold for every node, i.e. for
        min(p).
        """
        bound = 2.0 * self.p_min / (1.0 - topo.lambda_n + self.gamma * L)
        if self.theta >= bound:
            raise ValueError(
                f"theta={self.theta} >= Lemma-1 bound {bound:.4g} "
                f"(p={self.p}, lambda_n={topo.lambda_n:.4g})")


class SDMState(NamedTuple):
    x: PyTree       # public copy (stacked (n, ...) in reference; per-node distributed)
    s: PyTree       # weighted neighbour sum. In the DISTRIBUTED executor
    #                 this is a tuple of f32 wire planes (one (rows, LANE)
    #                 buffer per sharding bucket — see repro.core.plane);
    #                 the reference keeps the stacked tree.
    d: PyTree       # differential pending transmission (planes distributed)
    step: jax.Array  # iteration counter (int32)
    e: PyTree = None  # error-feedback residual (only when cfg.error_feedback)
    # Per-neighbour public-copy replicas (distributed executor, genuinely
    # time-varying schedules only): each PLANE gains a leading
    # (n_replicas,) axis — slot k tracks the union-round-k sender's
    # public copy x_j exactly, so s is recomputed FRESH with the current
    # round's weights (true W(t)-mixing). Memory cost: deg_union x model.
    xhat: PyTree = None
    # Overlapped transport double buffer (cfg.overlap only): the weighted
    # neighbour increments received by the exchange issued THIS step,
    # pending until the NEXT step folds them into s (one-step-stale
    # gossip). Planes in the distributed executor; stacked tree in the
    # reference.
    nb: PyTree = None


def _tree_zeros_like(t: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, t)


def _leaf_keys(key: jax.Array, tree: PyTree) -> PyTree:
    """One independent key per leaf, stable in tree-flatten order."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = [jax.random.fold_in(key, i) for i in range(len(leaves))]
    return jax.tree.unflatten(treedef, keys)


def _noise_like(key: jax.Array, tree: PyTree, sigma: float) -> PyTree:
    ks = _leaf_keys(key, tree)
    return jax.tree.map(
        lambda k, x: sigma * jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype),
        ks, tree)


def check_per_node_p(cfg, n_nodes: int) -> None:
    """Reject a per-node p tuple whose length mismatches the graph.

    Must be called wherever a config first meets a schedule: a too-short
    tuple would otherwise CLAMP on the distributed gather (every extra
    node silently reusing the last p — the wrong sparsity AND privacy
    budget) while the stacked reference vmap would crash, so the two
    executors would not even agree the config is valid.
    """
    if isinstance(getattr(cfg, "p", None), tuple) and len(cfg.p) != n_nodes:
        raise ValueError(
            f"per-node p has {len(cfg.p)} entries for {n_nodes} nodes")


def compressor_of(cfg) -> compressor_mod.Compressor:
    """The Compressor a config's wire format resolved to.

    Built once, when the ``SDMConfig`` was (``cfg.wire``), from either
    the ``compressor='...'`` spec or the legacy ``mode=`` spelling:
    sensitivity (``release_probability``), wire-cost accounting
    (``wire_elements`` / ``wire_bits``) and the transport
    (``transport``) live on the returned object.
    """
    return cfg.wire


@jax.named_scope("sdm_mask")
def masked_grad(grads: PyTree, key: jax.Array, *, sigma: float,
                clip_c: float | None) -> PyTree:
    """clip (optional, §5 procedure) then Gaussian-mask: g_hat = clip(g) + eta.

    The single noise/clipping implementation every method (SDM-DSGD,
    DSGD, DC-DSGD, gradient-push) shares — baselines used to rebuild an
    SDMConfig just to reach this (``DSGDConfig.as_sdm``, now gone).
    """
    if clip_c is not None:
        grads = clipping.clip_tree(grads, clip_c)
    if sigma > 0.0:
        noise = _noise_like(key, grads, sigma)
        grads = jax.tree.map(jnp.add, grads, noise)
        # the analyzer-visible sanitizer mark: ONLY the clipped+noised
        # gradient counts as DP-sanitized (sigma == 0 stays tainted).
        grads = tagging.sanitize(grads)
    return grads


def _masked_grad(grads: PyTree, key: jax.Array, cfg) -> PyTree:
    return masked_grad(grads, key, sigma=cfg.sigma, clip_c=cfg.clip_c)


def sparsify_planes_stacked(comp: compressor_mod.Compressor,
                            tree_stacked: PyTree, key: jax.Array, step,
                            n: int, transform=None) -> PyTree:
    """Plane-granular compressor roundtrip of a node-stacked tree.

    The ONE reference-executor implementation of "what each node puts on
    the wire": each bucket's zero-padded plane is compressed whole with
    key ``node_round_key(fold_in(key, bucket), node, step)`` — the exact
    key schedule and draw shape of the distributed plane transport.
    ``transform(payload, node)`` optionally rewrites the payload before
    the roundtrip (compressed push-sum's contraction scaling).
    """
    spec = plane_mod.ParamPlane.for_stacked(tree_stacked)
    planes = spec.pack_stacked(tree_stacked)
    out = []
    for b, dpl in enumerate(planes):
        bkey = jax.random.fold_in(key, b)
        node_keys = jax.vmap(
            lambda i: gossip.node_round_key(bkey, i, step))(jnp.arange(n))

        def one(i, k, v):
            pl = comp.compress(k, v, node=i)
            if transform is not None:
                pl = transform(pl, i)
            return comp.decompress(pl)

        out.append(jax.vmap(one)(jnp.arange(n), node_keys, dpl))
    return spec.unpack_stacked(tuple(out))


def schedule_degree_factor(seq, node: "int | None" = None) -> Fraction:
    """Payload transmissions per node per step on ``seq`` (exact Fraction).

    The per-link wire-accounting factor for the SDM transport: the mean
    (over the L rounds of the sequence) out-degree — 2 on the static
    symmetric ring, 1 on perfect-matching rounds; ``node=i`` uses node
    i's OWN out-degree where it differs (star hubs). Genuinely
    time-varying sequences run the replica transport (payloads cross
    every UNION edge every round), so their factor is the union-graph
    degree. ``seq=None`` callers keep the schedule-free legacy
    convention: one payload per step (factor 1).
    """
    if seq is None:
        return Fraction(1)
    seq = gossip.sequence_of(seq)
    return gossip.mean_out_degree(seq, union=gossip.needs_replicas(seq),
                                  node=node)


def wire_shape_tree(params: PyTree) -> Tuple[jax.ShapeDtypeStruct, ...]:
    """The plane-shaped tree the wire accounting runs over.

    The transport compresses the zero-padded (rows, LANE) planes, not
    the raw leaves, so cost accounting charges the PLANE geometry: one
    ``num_kept`` ceil over the whole plane per bucket (the round-once
    convention, now exact by construction) and plane-padded coordinate
    counts for dense/quantized payloads — byte-for-byte what the HLO
    collective-permutes actually move.

    Bucket-sensitive like the transport itself: ``ParamPlane.for_tree``
    consults the ``plane.use_buckets`` context, so accounting for a
    TP-bucketed run must be computed under the same context the step
    was traced in (``steps.plane_bucket_tree`` owns the policy); with
    no context both sides use the single flat bucket.
    """
    return plane_mod.ParamPlane.for_tree(params).shape_dtype()


def transmitted_elements_per_step(params: PyTree, cfg: SDMConfig,
                                  node: int | None = None, *,
                                  seq=None) -> int:
    """Expected non-zero elements one node transmits per iteration.

    The paper's Figure-3 communication metric ("non-zero digits"),
    charged at wire-plane granularity (see ``wire_shape_tree``): for
    fixedk modes this is exact; for bernoulli it is the expectation
    p * plane_size. With heterogeneous per-node p, ``node`` selects
    whose budget to count; ``node=None`` returns the across-node mean
    (exact-Fraction mean, rounded once — network total = mean *
    n_nodes). ``seq`` makes the count schedule-aware (per-link): the
    payload cost multiplies by the mean out-degree over the sequence's
    rounds (union-graph degree on the replica transport); ``seq=None``
    keeps the legacy one-payload-per-step convention.
    """
    comp = compressor_of(cfg)
    wire = wire_shape_tree(params)
    if isinstance(cfg.p, tuple) and node is None:
        exact = compressor_mod.node_mean_exact(
            cfg.p, lambda i: compressor_mod.tree_wire_elements_exact(
                comp, wire, node=i))
    else:
        exact = compressor_mod.tree_wire_elements_exact(comp, wire,
                                                        node=node)
    return int(round(exact * schedule_degree_factor(seq, node)))


def transmitted_bits_per_step(params: PyTree, cfg: SDMConfig,
                              node: int | None = None, *,
                              value_bits: int = 32,
                              index_sync: bool = True,
                              seq=None) -> int:
    """Exact WIRE BITS one node transmits per iteration.

    The honest companion to the element count, at wire-plane granularity
    (``wire_shape_tree`` — what the HLO payload actually is): packed
    formats also need an index side-channel at ceil(log2 d) bits per
    kept element — unless both endpoints regenerate index sets from the
    shared seed (``index_sync=True``, the repo's gossip transport),
    which removes index traffic entirely; quantizers ship every plane
    coordinate but at qsgd_bits instead of ``value_bits`` (sub-byte
    levels packed into u8 lanes, so the HLO bytes match too).
    ``node=None`` with per-node p returns the across-node mean
    (exact-Fraction mean, rounded once). ``seq`` applies the same
    per-link degree factor as the element count.
    """
    comp = compressor_of(cfg)
    wire = wire_shape_tree(params)
    kw = dict(value_bits=value_bits, index_sync=index_sync)
    if isinstance(cfg.p, tuple) and node is None:
        exact = compressor_mod.node_mean_exact(
            cfg.p, lambda i: compressor_mod.tree_wire_bits_exact(
                comp, wire, node=i, **kw))
    else:
        exact = compressor_mod.tree_wire_bits_exact(comp, wire, node=node,
                                                    **kw)
    return int(round(exact * schedule_degree_factor(seq, node)))


# ==========================================================================
# Reference simulator: n nodes stacked on axis 0, dense-W gossip.
# ==========================================================================

class ReferenceSimulator:
    """Single-host n-node stacked simulator (the paper's experiments).

    Accepts a ``Topology`` / ``DirectedTopology``, a ``PermuteSchedule``,
    or a time-varying ``ScheduleSequence`` — the reference executor and
    the distributed executor are built from the SAME schedule object, so
    their mixing matrices can never diverge.

    Static graphs (and weight-invariant sequences) mix with the exact
    dense W via the incremental neighbour sum ``s`` — byte-for-byte the
    historical trajectories. Genuinely time-varying sequences mix with
    the exact dense W(t) of the CURRENT round: the stacked public copies
    ``x`` are precisely what the distributed executor's per-neighbour
    replicas reconstruct, so ``commit`` computes W(t) x fresh each round
    — true W(t)-mixing, bit-comparable to an explicit dense simulator.
    Full-state methods (DSGD, gradient-push) stay exact on time-varying
    graphs by construction.
    """

    def __init__(self, topo, cfg: SDMConfig):
        self.cfg = cfg
        self.seq = gossip.sequence_of(topo)
        self.topo = None if isinstance(
            topo, (gossip.PermuteSchedule, gossip.ScheduleSequence)) else topo
        check_per_node_p(cfg, self.seq.n_nodes)
        # replica-exact: genuinely time-varying weights -> mix with the
        # full dense W(t) each round; otherwise the incremental-s fast
        # path (exact there, and byte-identical to the historical code).
        self.replica_exact = gossip.needs_replicas(self.seq)
        self.time_varying = self.seq.length > 1 and not self.replica_exact
        if cfg.overlap and self.replica_exact:
            raise ValueError(
                "overlap=True is a static-schedule (non-replica) transport: "
                "genuinely time-varying weights recompute s from replicas "
                "every round and cannot consume increments one step late")
        wstack = self.seq.weights_stack()
        self._wstack = jnp.asarray(wstack, jnp.float32)   # (L, n, n)
        self.weights = self._wstack[0]

    @property
    def n_nodes(self) -> int:
        return self.seq.n_nodes

    def _weights_at(self, step) -> jax.Array:
        return self._wstack[step % self.seq.length]

    def init(self, params_stack: PyTree) -> SDMState:
        """params_stack leaves have leading dim n (one slice per node)."""
        n = jax.tree.leaves(params_stack)[0].shape[0]
        assert n == self.seq.n_nodes, (n, self.seq.n_nodes)
        e = _tree_zeros_like(params_stack) if self.cfg.error_feedback else None
        if self.replica_exact:
            # commit mixes the full dense W(t) fresh each round: the
            # reference replica path carries NO neighbour-sum buffer.
            s = None
        elif self.time_varying or self.cfg.overlap:
            # incremental-s bookkeeping starts from the round-0 weights
            # (the distributed init does the same with (1 - W_ii(0)) x_0).
            # The overlapped transport maintains s incrementally even on
            # static graphs — the pending double buffer is an increment.
            s = jax.tree.map(
                lambda x: gossip.apply_weights_dense(
                    self._wstack[0], x, include_self=False).astype(x.dtype),
                params_stack)
        else:
            s = _tree_zeros_like(params_stack)
        nb = _tree_zeros_like(params_stack) if self.cfg.overlap else None
        return SDMState(x=params_stack, s=s,
                        d=_tree_zeros_like(params_stack),
                        step=jnp.zeros((), jnp.int32), e=e, nb=nb)

    # -- phase 1: everyone transmits S(d) and advances public copies ------
    def advance(self, state: SDMState, key: jax.Array) -> Tuple[SDMState, PyTree]:
        """Returns (state with x <- x + S(d), the S(d) stack)."""
        cfg = self.cfg
        n = self.seq.n_nodes

        if cfg.error_feedback:
            # fold the residual from the previous round into what we send.
            # EF requires the CONTRACTIVE (unscaled) compressor mask*d —
            # the unbiased 1/p amplification would make the residual loop
            # explosive; error feedback is what repairs the bias instead
            # (Stich et al.). Implemented by undoing the 1/p scale below.
            d_in = jax.tree.map(jnp.add, state.d, state.e)
        else:
            d_in = state.d
        ef_scale = cfg.p if cfg.error_feedback else 1.0

        # The compressor roundtrip (compress -> decompress) IS the
        # sparsifier S(.) each node applies before transmitting. Draws
        # happen at WIRE-PLANE granularity — one compress over each
        # bucket's zero-padded plane, exactly what the distributed
        # executor puts on the wire — so the two executors' bits can
        # never diverge (pad coordinates are zero and stay zero).
        sd = sparsify_planes_stacked(compressor_of(cfg), d_in, key,
                                     state.step, n)
        if cfg.error_feedback and ef_scale != 1.0:
            sd = jax.tree.map(lambda v: v * ef_scale, sd)
        x = jax.tree.map(jnp.add, state.x, sd)
        new_e = jax.tree.map(jnp.subtract, d_in, sd) \
            if cfg.error_feedback else state.e
        if cfg.overlap:
            # one-step-stale: fold the increments received LAST step into
            # s; this step's weighted increments (weights of the round
            # the payload crossed) wait in the pending buffer until the
            # next advance — exactly the distributed double buffer.
            w_t = self._weights_at(state.step)
            s = jax.tree.map(jnp.add, state.s, state.nb)
            nb = tagging.pending_buffer(jax.tree.map(
                lambda v, s_: gossip.apply_weights_dense(
                    w_t, v, include_self=False).astype(s_.dtype),
                sd, s))
            return state._replace(x=x, s=s, e=new_e, nb=nb), sd
        if self.time_varying:
            # fold this round's weighted increments into s — the weights
            # of the round the increment was EXCHANGED in, exactly what
            # the distributed executor accumulates.
            w_t = self._weights_at(state.step)
            s = jax.tree.map(
                lambda s_, v: s_ + gossip.apply_weights_dense(
                    w_t, v, include_self=False).astype(s_.dtype),
                state.s, sd)
            return state._replace(x=x, s=s, e=new_e), sd
        return state._replace(x=x, e=new_e), sd

    # -- phase 2: local gradient + masking + generalized mixing -----------
    def commit(self, state: SDMState, grads_stack: PyTree,
               key: jax.Array) -> SDMState:
        cfg = self.cfg
        g = _masked_grad(grads_stack, key, cfg)
        if self.replica_exact:
            # exact W(t)-mixing: the stacked x IS every node's public
            # copy, so mix with the CURRENT round's full dense matrix —
            # what the distributed executor reconstructs from replicas.
            mixed = jax.tree.map(
                lambda x: gossip.mix_dense(self._weights_at(state.step), x),
                state.x)
        elif self.time_varying or cfg.overlap:
            # W~(t) x for node i = W_ii(t) x_i + s_i (s incremental; under
            # overlap s carries the neighbours' one-step-STALE public
            # copies — the delayed-W-mixing semantics).
            diag_w = jnp.diagonal(self._weights_at(state.step))
            mixed = jax.tree.map(
                lambda x, s: diag_w.reshape(
                    (self.seq.n_nodes,) + (1,) * (x.ndim - 1)
                ).astype(x.dtype) * x + s,
                state.x, state.s)
        else:
            mixed = jax.tree.map(
                lambda x: gossip.mix_dense(self.weights, x), state.x)
        y = jax.tree.map(
            lambda x, m, gr: (1.0 - cfg.theta) * x + cfg.theta * (m - cfg.gamma * gr),
            state.x, mixed, g)
        d = jax.tree.map(jnp.subtract, y, state.x)
        return state._replace(d=d, step=state.step + 1)

    def step(self, state: SDMState, grad_fn, batch_stack: PyTree,
             key: jax.Array) -> Tuple[SDMState, PyTree]:
        """Convenience: advance -> grads at new x -> commit.

        grad_fn(params_stack, batch_stack) -> grads_stack, aux.
        Returns (new_state, aux).
        """
        k_sp, k_noise = jax.random.split(key)
        state, _ = self.advance(state, k_sp)
        grads, aux = grad_fn(state.x, batch_stack)
        state = self.commit(state, grads, k_noise)
        return state, aux

    def consensus_mean(self, state: SDMState) -> PyTree:
        """xbar_t = (1/n) sum_i x_{i,t} — the quantity Lemma 1 bounds."""
        return jax.tree.map(lambda x: jnp.mean(x, axis=0), state.x)

    # Method-protocol surface (repro.core.method): ``consensus`` is the
    # per-method consensus estimate, ``eval_params`` the per-node
    # parameter view evaluation should run on.
    consensus = consensus_mean

    def eval_params(self, state: SDMState) -> PyTree:
        return state.x


# ==========================================================================
# Distributed per-node step (inside shard_map; node axis manual).
# ==========================================================================

def _replica_planes(planes: Tuple[jax.Array, ...], n_replicas: int
                    ) -> Tuple[jax.Array, ...]:
    """Per-neighbour public-copy replica planes, all starting at x_0.

    Valid under the same identical-start assumption the s_0 formula uses:
    every neighbour's public copy begins at the shared x_0, and from then
    on slot k advances by exactly the increments the union-round-k sender
    transmits — so each slot stays an exact copy of x_{j,t} (as a plane).
    """
    return tuple(jnp.broadcast_to(p[None], (n_replicas,) + p.shape)
                 for p in planes)


def init_distributed_state(params: PyTree, self_weight,
                           n_replicas: int | None = None,
                           overlap: bool = False) -> SDMState:
    """Per-node state. ``params`` has NO node axis here (each shard owns one).

    All nodes must start from IDENTICAL params (standard same-seed init);
    then the initial neighbour sum is s_0 = (1 - W_ii) * x_0, since
    sum_{j != i} W_ij = 1 - W_ii and x_{j,0} = x_0. (The paper starts at
    x_0 = 0, a special case.) ``self_weight`` may be a python float or a
    traced scalar (``schedule.self_weight_of(me)`` inside shard_map, for
    topologies whose W_ii varies per node). ``n_replicas`` (genuinely
    time-varying schedules only) allocates the per-neighbour public-copy
    replica stack — deg_union extra plane buffers per node.

    ``s``, ``d`` (and ``xhat``) live as WIRE PLANES — f32 (rows, LANE)
    buffers, one per sharding bucket — because that is the shape the
    exchange consumes and produces; only ``x`` keeps the parameter tree
    (gradients are evaluated there).
    """
    spec = plane_mod.ParamPlane.for_tree(params)
    xp = spec.pack(params)
    s0 = tuple((1.0 - self_weight) * p for p in xp)
    d0 = tuple(jnp.zeros_like(p) for p in xp)
    xhat = _replica_planes(xp, n_replicas) if n_replicas else None
    if overlap and n_replicas:
        raise ValueError("overlap=True needs a static (non-replica) "
                         "schedule")
    nb0 = tuple(jnp.zeros_like(p) for p in xp) if overlap else None
    return SDMState(x=params, s=s0, d=d0,
                    step=jnp.zeros((), jnp.int32), xhat=xhat, nb=nb0)


@jax.named_scope("sdm_pack")
def _plane_payload_exchange(planes: Tuple[jax.Array, ...],
                            comp: compressor_mod.Compressor, *,
                            axis_name, base_key: jax.Array, step, me,
                            schedule=None, useq=None, node_index=None,
                            transform=None):
    """Compressor-payload transport over wire planes — the ONE copy.

    One compress per bucket plane (key ``node_round_key(fold_in(base,
    bucket), me, step)`` — the schedule ``sparsify_planes_stacked``
    mirrors in the reference); the payload crosses the static schedule's
    R rounds (``useq=None``, weighted sum) or every union round
    (``useq`` set, per-slot increment stacks). ``transform`` rewrites
    each payload pre-wire (compressed push-sum's contraction). Shared by
    SDM's "payload"-transport families AND compressed gradient-push, so
    the key schedule and contraction point cannot desynchronize between
    them.
    Returns (own decompressed planes, received planes).
    """
    own, recv = [], []
    for b, dp in enumerate(planes):
        key = gossip.node_round_key(
            jax.random.fold_in(base_key, b), me, step)
        pl = comp.compress(key, dp, node=me)
        if transform is not None:
            pl = transform(pl)
        own.append(comp.decompress(pl))
        if useq is not None:
            recv.append(gossip.union_exchange_payload(
                useq, pl, comp.decompress, axis_name))
        else:
            recv.append(gossip.exchange_payload(
                schedule, pl, comp.decompress, axis_name, step=step,
                node_index=node_index))
    return tuple(own), tuple(recv)


# The gossip calls behind the packed transports (``Compressor.transport``),
# as (static schedule, union schedule).
_PACKED_TRANSPORTS = {
    "blocks": (gossip.exchange_packed, gossip.union_exchange_packed),
    "rows": (gossip.exchange_packed_rows, gossip.union_exchange_packed_rows),
}


@jax.named_scope("sdm_pack")
def _plane_exchange(d_planes: Tuple[jax.Array, ...], *, axis_name,
                    base_key: jax.Array, step: jax.Array, cfg: SDMConfig,
                    me, schedule=None, useq=None, node_index=None
                    ) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """Plane-granular exchange: (own S(d) planes, received planes).

    The ONE transport behind every SDM wire format. Each bucket's plane
    is compressed/drawn ONCE (key ``fold_in(base, bucket)`` — the
    schedule the reference's ``sparsify_planes_stacked`` mirrors). On a
    static ``schedule`` it crosses the wire in exactly R
    collective-permutes per bucket, independent of the model's leaf
    count, and what is received is the weighted neighbour sum. On the
    union schedule ``useq`` of a time-varying sequence every union
    round's delivery lands in its OWN (n_replicas, rows, lane) row
    instead — one batched sender draw per bucket regardless of sequence
    length. The way the plane travels is the compressor family's
    ``transport``, chosen here once.
    """
    comp = compressor_of(cfg)
    if comp.transport == "payload":
        return _plane_payload_exchange(
            d_planes, comp, axis_name=axis_name, base_key=base_key,
            step=step, me=me, schedule=schedule, useq=useq,
            node_index=node_index)
    union = useq is not None
    if comp.transport == "dense":   # the masked plane itself crosses
        def one(dp, bkey):
            key = gossip.node_round_key(bkey, me, step)
            o = comp.decompress(comp.compress(key, dp, node=me))
            if union:
                return o, gossip.union_exchange(useq, o, axis_name)
            return o, gossip.exchange(schedule, o, axis_name,
                                      node_index=node_index, step=step)
    else:
        exchange = _PACKED_TRANSPORTS[comp.transport][union]
        kw = dict(axis_name=axis_name, step=step, p=cfg.p,
                  node_index=node_index)
        flat = comp.transport == "blocks"   # blocks of the flat plane
        if flat:
            kw["block"] = comp.block

        def one(dp, bkey):
            view = dp.reshape(-1) if flat else dp
            o, r = exchange(useq if union else schedule, view,
                            base_key=bkey, **kw)
            return o.reshape(dp.shape), r.reshape(r.shape[:-view.ndim]
                                                  + dp.shape)
    own, recv = zip(*(one(dp, jax.random.fold_in(base_key, b))
                      for b, dp in enumerate(d_planes)))
    return own, recv


def _replica_advance_exchange(d_planes: Tuple[jax.Array, ...],
                              xhat: Tuple[jax.Array, ...], *,
                              seq, axis_name, base_key: jax.Array,
                              step: jax.Array, cfg: SDMConfig, me,
                              node_index=None):
    """Shared replica-transport advance: (own planes, new xhat, fresh s).

    Every union in-neighbour's increment arrives tagged by round
    position, advances its replica slot, and the weighted neighbour sum
    is recomputed FRESH with the CURRENT round's weights — exact
    W(t)-mixing on B-connected sequences.
    """
    useq = gossip.union_schedule(seq)
    own, incr = _plane_exchange(
        d_planes, useq=useq, axis_name=axis_name, base_key=base_key,
        step=step, cfg=cfg, me=me, node_index=node_index)
    new_xhat = tuple(xh + inc for xh, inc in zip(xhat, incr))
    wv = gossip.replica_recv_weights(useq, me, step)     # (R,)
    s = tuple(jnp.tensordot(wv.astype(xh.dtype), xh, axes=([0], [0]))
              for xh in new_xhat)
    return own, new_xhat, s


def distributed_advance(state: SDMState, *, base_key: jax.Array, axis_name,
                        cfg: SDMConfig,
                        schedule=None,
                        self_weight: float | None = None,
                        neighbor_weight: float | None = None,
                        node_index=None) -> SDMState:
    """Phase 1 on the mesh: sparsify d, schedule-exchange, update x and s.

    ``schedule`` selects the gossip graph — a PermuteSchedule or a
    time-varying ScheduleSequence (indexed by the state's step counter);
    legacy scalar (self_weight, neighbor_weight) callers get the
    symmetric ring. ``node_index`` (optional sharded operand) replaces
    the axis_index collective where partial-auto shard_map cannot lower
    it. ``state.s`` / ``state.d`` (and ``state.xhat``) are wire planes.
    """
    del neighbor_weight  # ring default is fully described by self_weight
    seq = gossip.resolve_sequence(schedule, axis_name, self_weight)
    check_per_node_p(cfg, seq.n_nodes)
    me = gossip._me(axis_name, node_index)
    spec = plane_mod.ParamPlane.for_tree(state.x)

    if gossip.needs_replicas(seq):
        # genuinely time-varying weights: replica-correct advance (exact
        # W(t)-mixing; state.xhat must have been allocated at init).
        if cfg.overlap:
            raise ValueError("overlap=True needs a static (non-replica) "
                             "schedule")
        own, xhat, s = _replica_advance_exchange(
            state.d, state.xhat, seq=seq, axis_name=axis_name,
            base_key=base_key, step=state.step, cfg=cfg, me=me,
            node_index=node_index)
        x = jax.tree.map(jnp.add, state.x, spec.unpack(own))
        return state._replace(x=x, s=s, xhat=xhat)

    own, nb = _plane_exchange(
        state.d, schedule=seq, axis_name=axis_name, base_key=base_key,
        step=state.step, cfg=cfg, me=me, node_index=node_index)
    with jax.named_scope("sdm_mix"):
        x = jax.tree.map(jnp.add, state.x, spec.unpack(own))
        s = tuple(s_ + nb_ for s_, nb_ in
                  zip(state.s, state.nb if cfg.overlap else nb))
    if cfg.overlap:
        # Overlapped transport: this step's mixing consumes the PENDING
        # buffer (last step's exchange result) and the fresh exchange
        # lands in the double buffer for the next step. Nothing after
        # this point in the step reads ``nb``, so the permute's data
        # dependency ends at the loop carry — XLA's async scheduler is
        # free to issue collective-permute-start here and sink the
        # matching -done past the entire gradient computation of the
        # next iteration.
        return state._replace(x=x, s=s, nb=tagging.pending_buffer(nb))
    return state._replace(x=x, s=s)


class SDMFusedState(NamedTuple):
    """Two-buffer state for the fused step (see distributed_step_fused).

    On genuinely time-varying schedules the replica stack ``xhat`` rides
    along (deg_union extra buffers) — the price of exact W(t)-mixing.
    """
    x: PyTree
    s: PyTree
    step: jax.Array
    xhat: PyTree = None
    nb: PyTree = None   # overlap double buffer (see SDMState.nb)


def init_fused_state(params: PyTree, self_weight,
                     n_replicas: int | None = None,
                     overlap: bool = False) -> SDMFusedState:
    xp = plane_mod.ParamPlane.for_tree(params).pack(params)
    s0 = tuple((1.0 - self_weight) * p for p in xp)
    xhat = _replica_planes(xp, n_replicas) if n_replicas else None
    if overlap and n_replicas:
        raise ValueError("overlap=True needs a static (non-replica) "
                         "schedule")
    nb0 = tuple(jnp.zeros_like(p) for p in xp) if overlap else None
    return SDMFusedState(x=params, s=s0, step=jnp.zeros((), jnp.int32),
                         xhat=xhat, nb=nb0)


def distributed_step_fused(state: SDMFusedState, grads: PyTree, *,
                           base_key: jax.Array, axis_name, cfg: SDMConfig,
                           schedule=None,
                           self_weight: float | None = None,
                           neighbor_weight: float | None = None,
                           node_index=None) -> SDMFusedState:
    """Memory-optimized whole-iteration step: commit_t + advance_{t+1} fused.

    Identical algorithm to (distributed_advance; grads; distributed_commit)
    with the step boundary shifted by half an iteration: the differential
    d_t only lives INSIDE the step (computed from this step's gradient,
    sparsified, exchanged, and folded into (x, s) immediately), so the
    persistent state drops from 3 parameter buffers (x, s, d) to 2 —
    a 1/3 cut of the dominant memory term. Gradient must be evaluated at
    state.x BEFORE calling (x is already post-advance).
    """
    del neighbor_weight
    seq = gossip.resolve_sequence(schedule, axis_name, self_weight)
    check_per_node_p(cfg, seq.n_nodes)
    me = gossip._me(axis_name, node_index)
    sw = seq.self_weight_of(me, state.step)
    noise_key = jax.random.fold_in(
        gossip.node_round_key(base_key, me, state.step), 0x5eed)
    g = _masked_grad(grads, noise_key, cfg)
    spec = plane_mod.ParamPlane.for_tree(state.x)
    xp = spec.pack(state.x)
    gp = spec.pack(g)
    d = tuple(cfg.theta * (sw * x_ + s_ - cfg.gamma * g_) - cfg.theta * x_
              for x_, s_, g_ in zip(xp, state.s, gp))

    # immediately sparsify + exchange + fold in (the next round's advance).
    # Sparsifier keys use counter step+1: in the unfused flow d_t is
    # sparsified by the NEXT iteration's advance (bit-equality preserved;
    # for a time-varying sequence the exchange likewise runs on the
    # NEXT round's graph).
    sp_step = state.step + 1
    if gossip.needs_replicas(seq):
        if cfg.overlap:
            raise ValueError("overlap=True needs a static (non-replica) "
                             "schedule")
        own, xhat, s = _replica_advance_exchange(
            d, state.xhat, seq=seq, axis_name=axis_name, base_key=base_key,
            step=sp_step, cfg=cfg, me=me, node_index=node_index)
        x = jax.tree.map(jnp.add, state.x, spec.unpack(own))
        return SDMFusedState(x=x, s=s, step=state.step + 1, xhat=xhat)
    own, nb = _plane_exchange(
        d, schedule=seq, axis_name=axis_name, base_key=base_key,
        step=sp_step, cfg=cfg, me=me, node_index=node_index)
    x = jax.tree.map(jnp.add, state.x, spec.unpack(own))
    if cfg.overlap:
        # one-step-stale double buffer (see distributed_advance).
        s = tuple(s_ + p_ for s_, p_ in zip(state.s, state.nb))
        return SDMFusedState(x=x, s=s, step=state.step + 1,
                             nb=tagging.pending_buffer(nb))
    s = tuple(s_ + nb_ for s_, nb_ in zip(state.s, nb))
    return SDMFusedState(x=x, s=s, step=state.step + 1)


def distributed_commit(state: SDMState, grads: PyTree, *, base_key: jax.Array,
                       axis_name, cfg: SDMConfig,
                       schedule=None,
                       self_weight: float | None = None,
                       node_index=None) -> SDMState:
    """Phase 2 on the mesh: masked gradient + generalized mixing update.

    Runs on the wire planes: x and the masked gradient are packed once
    (cheap reshape/concat, fused by XLA) and the differential is
    produced directly in plane form — ready for the next advance's
    single-draw exchange.
    """
    seq = gossip.resolve_sequence(schedule, axis_name, self_weight)
    me = gossip._me(axis_name, node_index)
    sw = seq.self_weight_of(me, state.step)
    noise_key = jax.random.fold_in(
        gossip.node_round_key(base_key, me, state.step), 0x5eed)
    g = _masked_grad(grads, noise_key, cfg)
    with jax.named_scope("sdm_mix"):
        spec = plane_mod.ParamPlane.for_tree(state.x)
        xp = spec.pack(state.x)
        gp = spec.pack(g)
        # W~ x for node i = W_ii x_i + s_i  (s maintained incrementally
        # on static schedules, recomputed from the exact replicas on
        # time-varying ones — either way it carries this round's
        # weights).
        y = tuple((1.0 - cfg.theta) * x_
                  + cfg.theta * (sw * x_ + s_ - cfg.gamma * g_)
                  for x_, s_, g_ in zip(xp, state.s, gp))
        d = tuple(y_ - x_ for y_, x_ in zip(y, xp))
    return state._replace(d=d, step=state.step + 1)
