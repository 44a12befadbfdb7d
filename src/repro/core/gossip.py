"""Gossip exchange primitives: dense-W reference and TPU mesh collectives.

Interchangeable realizations of "each node sends its (sparsified)
message to its graph neighbours":

* ``mix_dense``        — reference: einsum with the full (n, n) consensus
                         matrix over a node-stacked leading axis. Used by
                         the single-host simulator and all correctness
                         tests; supports arbitrary topologies (ER graphs).
* ``exchange``         — distributed, ANY static topology: a compiled
                         ``PermuteSchedule`` of `jax.lax.ppermute` rounds.
                         Lowers to TPU `collective-permute`. Dense payload
                         (paper-faithful Bernoulli-masked tensors).
* ``exchange_packed`` / ``exchange_packed_rows``
                       — distributed + communication-real: only the
                         k = ceil(p*d) selected values cross the wire;
                         the index set is regenerated on the receiver from
                         the (round, sender) seed. Collective bytes shrink
                         by exactly p. (DESIGN.md §2.) A node's OWN S(d)
                         never goes through that payload: its keep set is
                         drawn as a mask (``sparsifier.fixedk_mask``) and
                         applied as a dense select.
* ``ring_exchange``    — the original hand-written degree-2 symmetric-ring
                         specialization, kept as the minimal-latency fast
                         path and for backward compatibility.

Schedule design
---------------
``schedule_from_topology`` compiles a ``Topology`` into a static
``PermuteSchedule``: the graph's directed edges are grouped by cyclic
shift s = (receiver - sender) mod n (see
``topology.shift_decomposition``), and each shift class becomes one
partial ``ppermute`` whose sources/destinations are exactly that class's
edges. Receivers that are not a destination in a round get ppermute's
implicit zeros. Per-edge consensus weights W_ij are applied locally by
the receiver: round s carries a per-node weight vector
``w_s[r] = W[r, (r-s) % n]`` (zero on non-edges), embedded as a constant
and indexed by ``axis_index``. The weighted neighbour sum is therefore

    sum_s w_s[me] * ppermute_s(x)  ==  sum_{j in N_i} W_ij x_j,

with one collective-permute per distinct shift: 2 rounds for the
symmetric ring, 4 for a 2-D torus, up to n-1 for dense ER graphs — all
with static shapes, so packed fixed-k payloads work unchanged: the
shift-s sender of node ``me`` is ``(me - s) % n``, whose index set the
receiver regenerates from ``node_round_key``. Self-weights W_ii may
differ per node (Metropolis–Hastings graphs);
``PermuteSchedule.self_weight_of(me)`` resolves them on-mesh.

All distributed functions must be called inside `jax.shard_map` with the
node axis manual.
"""
from __future__ import annotations

import dataclasses
import functools
from fractions import Fraction
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparsifier, tagging

# Trace-time record of the fixed-k keep-set draws built into programs:
# ``own_mask`` counts own S(d) keep sets drawn as masks
# (``_own_and_payload``); ``compact`` the wire's index lists a step keeps,
# each a counted mask compacted to ascending order (the sender's payload
# list, and one per sender the receivers regenerate). Read with
# ``draw_counts``.
_DRAWS = {"own_mask": 0, "compact": 0}

__all__ = [
    "mix_dense",
    "apply_weights_dense",
    "PermuteSchedule",
    "ScheduleRound",
    "ScheduleSequence",
    "UnionRound",
    "UnionSchedule",
    "union_schedule",
    "needs_replicas",
    "weight_invariant",
    "mean_out_degree",
    "replica_recv_weights",
    "schedule_from_topology",
    "sequence_from_topologies",
    "sequence_by_name",
    "ensure_sequence",
    "ring_schedule",
    "resolve_schedule",
    "resolve_sequence",
    "exchange",
    "exchange_payload",
    "exchange_packed",
    "exchange_packed_rows",
    "union_exchange",
    "union_exchange_payload",
    "union_exchange_packed",
    "union_exchange_packed_rows",
    "ring_exchange",
    "ring_weighted_neighbor_sum",
    "node_round_key",
    "draw_counts",
]


# --------------------------------------------------------------------------
# Reference (single-host, node-stacked) path.
# --------------------------------------------------------------------------

def mix_dense(weights: jax.Array, x_stack: jax.Array) -> jax.Array:
    """(W x)_i = sum_j W_ij x_j over the leading node axis."""
    return jnp.einsum("ij,j...->i...", weights, x_stack)


def apply_weights_dense(weights: jax.Array, msgs_stack: jax.Array,
                        include_self: bool = False) -> jax.Array:
    """Weighted neighbour sum sum_{j != i} W_ij msg_j (optionally + W_ii msg_i)."""
    w = weights if include_self else weights - jnp.diag(jnp.diag(weights))
    return jnp.einsum("ij,j...->i...", w, msgs_stack)


# --------------------------------------------------------------------------
# Static permute schedules: any Topology -> ppermute rounds.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScheduleRound:
    """One ppermute round: all edges with (receiver - sender) % n == shift."""

    shift: int
    perm: Tuple[Tuple[int, int], ...]       # (src, dst) pairs, partial perm
    recv_weights: Tuple[float, ...]         # (n,) W[r, (r-shift) % n] or 0


@dataclasses.dataclass(frozen=True)
class PermuteSchedule:
    """A Topology compiled to static collective-permute rounds.

    Hashable/static: safe to close over in jit/shard_map. ``rounds`` has
    one entry per distinct cyclic shift present in the adjacency;
    ``self_weights[i] = W_ii`` (may vary per node, e.g. MH weights).
    """

    name: str
    n_nodes: int
    self_weights: Tuple[float, ...]
    rounds: Tuple[ScheduleRound, ...]

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)

    def self_weight_of(self, me) -> jax.Array:
        """W_ii for the calling node (index with axis_index inside shard_map)."""
        return jnp.asarray(self.self_weights, jnp.float32)[me]

    def neighbor_weight_sums(self) -> Tuple[float, ...]:
        """Row sums minus the diagonal: sum_{j != i} W_ij per node.

        For doubly stochastic W this is 1 - W_ii; for column-stochastic
        push matrices rows do NOT sum to 1, so compressed push-sum init
        (s_0 = sum_{j != i} P_ij x_0) needs the true per-node row sum.
        """
        n = self.n_nodes
        sums = [0.0] * n
        for rnd in self.rounds:
            for r in range(n):
                sums[r] += rnd.recv_weights[r]
        return tuple(sums)

    def dense_weights(self) -> np.ndarray:
        """Reconstruct the full (n, n) consensus/push matrix W.

        Inverse of ``schedule_from_topology``: W_ii from ``self_weights``
        and W[r, (r - s) % n] from round s's receive weights. Reference
        executors mix with exactly this matrix, so both executors are
        built from the same schedule object.
        """
        n = self.n_nodes
        w = np.diag(np.asarray(self.self_weights, np.float64))
        for rnd in self.rounds:
            for r in range(n):
                if rnd.recv_weights[r]:
                    w[r, (r - rnd.shift) % n] = rnd.recv_weights[r]
        return w


@dataclasses.dataclass(frozen=True)
class ScheduleSequence:
    """A (possibly time-varying) gossip schedule: one PermuteSchedule per
    round, cycled by the iteration counter (B-connected sequences).

    Static graphs are the length-1 special case. Hashable/static like
    ``PermuteSchedule`` — safe to close over in jit/shard_map; the
    *traced* step counter picks the active schedule at runtime via
    ``lax.switch`` in the exchange helpers.
    """

    name: str
    n_nodes: int
    schedules: Tuple[PermuteSchedule, ...]

    def __post_init__(self) -> None:
        if not self.schedules:
            raise ValueError("ScheduleSequence needs >= 1 schedule")
        if any(s.n_nodes != self.n_nodes for s in self.schedules):
            raise ValueError("all schedules must share n_nodes")

    @property
    def length(self) -> int:
        return len(self.schedules)

    @property
    def n_rounds(self) -> int:
        """Worst-case collective-permute rounds per gossip step."""
        return max(s.n_rounds for s in self.schedules)

    def at(self, t: int) -> PermuteSchedule:
        """The schedule active at (python int) iteration t."""
        return self.schedules[int(t) % self.length]

    def self_weight_of(self, me, step=None) -> jax.Array:
        """W_ii(step) for the calling node; ``step`` may be traced."""
        if self.length == 1 or step is None:
            return self.schedules[0].self_weight_of(me)
        table = jnp.asarray([s.self_weights for s in self.schedules],
                            jnp.float32)          # (L, n)
        return table[step % self.length, me]

    def weights_stack(self) -> np.ndarray:
        """(L, n, n) stacked dense matrices (reference-executor mixing)."""
        return np.stack([s.dense_weights() for s in self.schedules])


def ensure_sequence(schedule) -> ScheduleSequence:
    """Wrap a single PermuteSchedule as a length-1 ScheduleSequence."""
    if isinstance(schedule, ScheduleSequence):
        return schedule
    return ScheduleSequence(name=schedule.name, n_nodes=schedule.n_nodes,
                            schedules=(schedule,))


def sequence_of(topo) -> ScheduleSequence:
    """Normalize ANY graph argument to a ScheduleSequence.

    Accepts a ScheduleSequence, a PermuteSchedule, or a (Directed)Topology
    — the single conversion every reference executor and the trainer use,
    so graph handling cannot drift between them.
    """
    if isinstance(topo, (PermuteSchedule, ScheduleSequence)):
        return ensure_sequence(topo)
    return ensure_sequence(schedule_from_topology(topo))


def schedule_from_topology(topo) -> PermuteSchedule:
    """Compile ``topo`` (a topology.Topology) into a PermuteSchedule."""
    from repro.core import topology as topology_mod

    adj = np.asarray(topo.adjacency)
    n = topo.n_nodes
    rounds = []
    for shift, pairs in sorted(topology_mod.shift_decomposition(adj).items()):
        rw = topology_mod.shift_receive_weights(topo, shift)
        rounds.append(ScheduleRound(
            shift=shift,
            perm=tuple((int(a), int(b)) for a, b in pairs),
            recv_weights=tuple(float(v) for v in rw)))
    return PermuteSchedule(
        name=topo.name, n_nodes=n,
        self_weights=tuple(float(topo.weights[i, i]) for i in range(n)),
        rounds=tuple(rounds))


def sequence_from_topologies(topos, name: str | None = None
                             ) -> ScheduleSequence:
    """Compile a list of topologies into a time-varying ScheduleSequence."""
    schedules = tuple(schedule_from_topology(t) for t in topos)
    return ScheduleSequence(
        name=name or "+".join(s.name for s in schedules)[:64],
        n_nodes=schedules[0].n_nodes, schedules=schedules)


def sequence_by_name(spec: str, n_nodes: int, *,
                     self_weight: float | None = None,
                     seed: int = 0, placement: bool = False
                     ) -> ScheduleSequence:
    """Parse a CLI spec into a ScheduleSequence.

    Static ``topology.by_name`` specs give a length-1 sequence;
    ``matchings`` / ``matchings:<L>`` gives L random per-round matchings
    (B-connected time-varying gossip), cycled by the step counter.

    ``placement=True`` renumbers the logical nodes with
    ``topology.greedy_placement`` before compiling, so high-traffic
    shifts land on nearest-neighbour ICI permutations (time-varying
    sequences place their UNION graph — one consistent renumbering for
    every round). Spectrum-preserving (``apply_placement`` permutes W
    symmetrically) and monotone: applied only when it strictly lowers
    the ring-hop cost, so optimal layouts compile byte-identically.
    """
    from repro.core import topology as topology_mod

    def placed(topos):
        if not placement:
            return topos
        union = np.zeros((n_nodes, n_nodes), dtype=np.int64)
        for t in topos:
            union |= np.asarray(t.adjacency, dtype=np.int64)
        order = topology_mod.greedy_placement(union)
        if topology_mod.placement_cost(union, order) < \
                topology_mod.placement_cost(union):
            return [topology_mod.apply_placement(t, order) for t in topos]
        return topos

    spec = spec.strip().lower()
    if spec.startswith("matchings") and n_nodes > 1:
        rounds = int(spec.split(":", 1)[1]) if ":" in spec else 4
        topos = placed(topology_mod.random_matchings(
            n_nodes, rounds, seed=seed,
            self_weight=0.5 if self_weight is None else self_weight))
        return sequence_from_topologies(
            topos, name=f"matchings{n_nodes}x{rounds}_s{seed}")
    if spec.startswith("matchings"):    # n_nodes == 1 degenerate
        spec = "complete"
    topo = topology_mod.by_name(spec, n_nodes, self_weight=self_weight,
                                seed=seed)
    [topo] = placed([topo])
    return ensure_sequence(schedule_from_topology(topo))


def sequence_from_active_sets(topo, active_sets, name: str | None = None
                              ) -> ScheduleSequence:
    """Compile a partial-participation trace into a ScheduleSequence.

    ``active_sets`` is one iterable of participating node indices per
    round (the edge-fleet simulator's sampled subgraphs); each round
    compiles the induced ``topology.masked_subgraph`` — inactive nodes
    isolated, active-active edges reweighted on the induced graph. The
    result is an ordinary (usually genuinely time-varying, hence
    replica-transported) sequence, so every executor and the analyzer
    matrix consume it like any other schedule.
    """
    active_sets = list(active_sets)
    if not active_sets:
        raise ValueError("need >= 1 active set")
    from repro.core import topology as topology_mod

    topos = [topology_mod.masked_subgraph(topo, a,
                                          name=f"{topo.name}_sub_r{t}")
             for t, a in enumerate(active_sets)]
    return sequence_from_topologies(
        topos, name=name or f"{topo.name}_part{len(active_sets)}")


# --------------------------------------------------------------------------
# Union schedules: the replica-correct transport for time-varying sequences.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UnionRound:
    """One ppermute round of the UNION graph of a schedule sequence.

    ``perm`` carries every directed edge with this cyclic shift that
    appears in ANY round of the sequence; ``recv_weights[t][r]`` is the
    weight W^{(t)}[r, (r - shift) % n] the edge carries at sequence
    position t (zero when the edge is inactive that round — the payload
    still crosses so the receiver's replica stays exact).
    """

    shift: int
    perm: Tuple[Tuple[int, int], ...]
    recv_weights: Tuple[Tuple[float, ...], ...]     # (L, n)


@dataclasses.dataclass(frozen=True)
class UnionSchedule:
    """The union graph of a ScheduleSequence compiled to ppermute rounds.

    The transport of the replica-correct time-varying executors: payloads
    cross EVERY union edge EVERY round (so receivers see every increment
    and per-neighbour public-copy replicas are exact by construction),
    while the mixing weights vary with the sequence position. Delivery is
    round-invariant, so no ``lax.switch`` is needed on this path — only
    the (step % L)-indexed weight gather depends on the traced step.

    Each round contributes at most one in-neighbour per node (the shift-s
    sender of ``me`` is ``(me - s) % n``), so ``n_replicas`` replica
    slots — one per union round, "tagged by sender round-position" —
    index every possible in-neighbour with one static shape.
    """

    name: str
    n_nodes: int
    length: int
    rounds: Tuple[UnionRound, ...]

    @property
    def n_replicas(self) -> int:
        """Replica slots per node: one per union shift round."""
        return len(self.rounds)

    def mean_out_degree(self) -> Fraction:
        """Mean (over nodes) union out-degree — payload transmissions per
        node per gossip step on the replica transport (same every round)."""
        edges = sum(len(rnd.perm) for rnd in self.rounds)
        return Fraction(edges, self.n_nodes)


@functools.lru_cache(maxsize=None)
def union_schedule(seq: ScheduleSequence) -> UnionSchedule:
    """Compile the union graph of ``seq`` with per-position edge weights."""
    seq = ensure_sequence(seq)
    n = seq.n_nodes
    edges_by_shift: dict = {}
    for sched in seq.schedules:
        shifts = [rnd.shift for rnd in sched.rounds]
        if len(shifts) != len(set(shifts)):
            # the per-position weight table below keys on (shift, t); two
            # same-shift rounds in one schedule would silently drop one
            # round's weights (the static executors SUM deliveries per
            # round, so they accept such schedules — we must not diverge
            # silently). Factory schedules (shift_decomposition) are safe.
            raise ValueError(
                f"union_schedule: schedule {sched.name!r} has duplicate "
                f"shifts {shifts}; merge same-shift rounds first")
        for rnd in sched.rounds:
            edges_by_shift.setdefault(rnd.shift, set()).update(rnd.perm)
    rounds = []
    for shift in sorted(edges_by_shift):
        rw = []
        for sched in seq.schedules:
            w_t = (0.0,) * n
            for rnd in sched.rounds:
                if rnd.shift == shift:
                    w_t = rnd.recv_weights
            rw.append(tuple(w_t))
        rounds.append(UnionRound(
            shift=shift,
            perm=tuple(sorted(edges_by_shift[shift])),
            recv_weights=tuple(rw)))
    return UnionSchedule(name=f"union({seq.name})", n_nodes=n,
                         length=seq.length, rounds=tuple(rounds))


@functools.lru_cache(maxsize=None)
def weight_invariant(seq: ScheduleSequence) -> bool:
    """True when every round of the sequence mixes with the SAME dense W.

    Then incremental neighbour-sum bookkeeping is exact (the weights an
    increment was folded with never differ from the current round's) and
    the replica transport is unnecessary.
    """
    ws = seq.weights_stack()
    return all(np.array_equal(ws[0], w) for w in ws[1:])


def needs_replicas(seq) -> bool:
    """Whether differential methods need per-neighbour replicas on ``seq``.

    Static schedules (and weight-invariant sequences) keep the
    incremental-``s`` fast path — byte-for-byte the pre-replica
    trajectories; genuinely time-varying weights need exact public-copy
    replicas for true W(t)-mixing.
    """
    seq = ensure_sequence(seq)
    return seq.length > 1 and not weight_invariant(seq)


def mean_out_degree(seq, *, union: bool = False,
                    node: "int | None" = None) -> Fraction:
    """Mean-over-rounds directed out-degree of the transport.

    The per-link wire-accounting factor: how many copies of its payload a
    node puts on the wire per gossip step — 2 for the symmetric ring, 1
    for perfect-matching rounds, the union-graph degree for the replica
    transport (``union=True``: every union edge carries the payload every
    round). ``node=None`` averages over nodes (the network-mean
    accounting convention); ``node=i`` counts node i's OWN out-edges
    (out-degree varies per node on e.g. star graphs). Exact Fraction so
    tree-level accounting can round ONCE.
    """
    seq = ensure_sequence(seq)

    def count(perm) -> int:
        if node is None:
            return len(perm)
        return sum(1 for src, _ in perm if src == node)

    denom = 1 if node is not None else seq.n_nodes
    if union:
        u = union_schedule(seq)
        return Fraction(sum(count(rnd.perm) for rnd in u.rounds), denom)
    total = sum(sum(count(rnd.perm) for rnd in s.rounds)
                for s in seq.schedules)
    return Fraction(total, denom * seq.length)


def replica_recv_weights(useq: UnionSchedule, me, step) -> jax.Array:
    """(n_replicas,) weights W_{me, sender_k}(step) for the replica slots.

    ``me`` and ``step`` may be traced; the (R, L, n) weight table is a
    closed-over constant, so this lowers to one gather — no collectives,
    no ``lax.switch``.
    """
    table = jnp.asarray([rnd.recv_weights for rnd in useq.rounds],
                        jnp.float32)            # (R, L, n)
    return table[:, step % useq.length, me]


def union_exchange(useq: UnionSchedule, x: jax.Array, axis_name) -> jax.Array:
    """ppermute ``x`` over every union round; (n_replicas, *x.shape) stack.

    Row k is the increment received from the shift-s_k sender (ppermute's
    implicit zeros where the union graph has no such in-edge — the slot's
    weight is zero at every sequence position, so the unused replica is
    never read).
    """
    return jnp.stack([_wire_ppermute(x, axis_name, rnd.perm)
                      for rnd in useq.rounds])


def union_exchange_payload(useq: UnionSchedule, payload, decompress,
                           axis_name) -> jax.Array:
    """Decompressed per-slot increments of a compressor payload.

    The replica-transport sibling of ``exchange_payload``: the payload
    pytree crosses every union round and the receiver decompresses each
    round's delivery SEPARATELY (tagged by round position) instead of
    folding a weighted sum — the caller adds row k onto replica slot k.
    """
    outs = []
    for rnd in useq.rounds:
        recv = jax.tree.map(
            lambda v: _wire_ppermute(v, axis_name, rnd.perm), payload)
        outs.append(decompress(recv))
    return jnp.stack(outs)


@jax.named_scope("sdm_pack")
def _union_packed_exchange(useq: UnionSchedule, db: jax.Array, to_leaf, *,
                           axis_name, base_key: jax.Array, step: jax.Array,
                           p, node_index) -> Tuple[jax.Array, jax.Array]:
    """Packed replica transport on a (2-D block view of a) leaf.

    The own S(d) and the payload (``_own_and_payload``) are those of
    the static ``_packed_exchange`` transport (same keys,
    same pad-to-max-k heterogeneous-p payloads), but each union round's
    received values are unpacked into their OWN increment row instead of
    a weighted sum — one batched draw of the senders' index lists per
    (leaf, step) regardless of sequence length.
    """
    nb_blocks = db.shape[0]
    me = _me(axis_name, node_index)
    own_sparse, kb, my_vals = _own_and_payload(
        db, to_leaf, p, me, base_key=base_key, step=step)
    _DRAWS["compact"] += 1
    sender_idx = _batched_sender_indices(
        useq, me, base_key=base_key, step=step, nb=nb_blocks, kb=kb)
    incr = jnp.stack([
        to_leaf(_unpack(db, _wire_ppermute(my_vals, axis_name, rnd.perm),
                        sender_idx[i]))
        for i, rnd in enumerate(useq.rounds)])
    return own_sparse, incr


def union_exchange_packed(useq: UnionSchedule, d_flat: jax.Array, *,
                          axis_name, base_key: jax.Array, step: jax.Array,
                          p, block: int = 1,
                          node_index=None) -> Tuple[jax.Array, jax.Array]:
    """Replica-transport packed gossip; returns (own_sparse, (R, dim) incr)."""
    dim = d_flat.shape[0]
    return _union_packed_exchange(
        useq, sparsifier.block_view(d_flat, block),
        lambda rows: rows.reshape(-1)[:dim], axis_name=axis_name,
        base_key=base_key, step=step, p=p, node_index=node_index)


def union_exchange_packed_rows(useq: UnionSchedule, d: jax.Array, *,
                               axis_name, base_key: jax.Array,
                               step: jax.Array, p,
                               node_index=None
                               ) -> Tuple[jax.Array, jax.Array]:
    """Sharding-aligned packed replica transport (blocks = rows)."""
    return _union_packed_exchange(
        useq, _row_view(d), lambda rows: rows.reshape(d.shape),
        axis_name=axis_name, base_key=base_key, step=step, p=p,
        node_index=node_index)


@functools.lru_cache(maxsize=None)
def ring_schedule(n: int, self_weight: float | None = None) -> PermuteSchedule:
    """The symmetric ring as a schedule (2 rounds: shifts +1 and n-1)."""
    from repro.core import topology as topology_mod

    return schedule_from_topology(topology_mod.ring(n, self_weight))


def resolve_schedule(schedule: PermuteSchedule | None, axis_name,
                     self_weight: float | None = None) -> PermuteSchedule:
    """Back-compat shim: default to the ring over the full node axis.

    Legacy callers pass scalar (self_weight, neighbor_weight) instead of a
    schedule; the axis size is static under shard_map tracing, so the ring
    schedule can be built on the fly.
    """
    if schedule is not None:
        if isinstance(schedule, ScheduleSequence):
            if schedule.length != 1:
                raise ValueError(
                    "time-varying sequence passed where a single static "
                    "schedule is required; use resolve_sequence")
            return schedule.schedules[0]
        return schedule
    n = int(jax.lax.psum(1, axis_name))
    return ring_schedule(n, self_weight)


def resolve_sequence(schedule, axis_name,
                     self_weight: float | None = None) -> ScheduleSequence:
    """Normalize PermuteSchedule | ScheduleSequence | None to a sequence.

    ``None`` keeps the legacy behaviour: the symmetric ring over the
    full node axis with scalar ``self_weight``.
    """
    if schedule is None:
        n = int(jax.lax.psum(1, axis_name))
        schedule = ring_schedule(n, self_weight)
    return ensure_sequence(schedule)


def _me(axis_name, node_index):
    """The caller's node index: explicit operand, or axis_index collective."""
    if node_index is not None:
        return node_index
    return jax.lax.axis_index(axis_name)


@jax.named_scope("sdm_permute")
def _wire_ppermute(x: jax.Array, axis_name, perm) -> jax.Array:
    """The ONE ppermute call site of the transport layer.

    Every buffer this module puts on the wire goes through here, tagged
    ``tagging.wire_payload`` so ``repro.analysis`` can prove (a) no
    collective-permute bypasses the vetted transport and (b) the operand
    carries no unsanitized data-taint. Identity at runtime.
    """
    return jax.lax.ppermute(tagging.wire_payload(x), axis_name, perm)


def _round_weight(rnd: ScheduleRound, me, dtype) -> jax.Array:
    return jnp.asarray(rnd.recv_weights, jnp.float32)[me].astype(dtype)


def exchange(schedule, x: jax.Array, axis_name,
             node_index=None, step=None) -> jax.Array:
    """Weighted neighbour sum sum_{j in N_i(t)} W_ij(t) x_j, dense payload.

    One ppermute per schedule round; receivers with no shift-s in-edge get
    ppermute zeros and a zero weight, so the sum is exact on any graph.
    ``schedule`` may be a single PermuteSchedule or a time-varying
    ScheduleSequence — the latter needs the (possibly traced) ``step``
    counter, and lowers to a ``lax.switch`` over the per-round branches so
    only the active round's permutes execute. ``node_index`` overrides
    `axis_index` where that collective cannot lower (partial-auto
    shard_map on older jaxlibs).
    """
    seq = ensure_sequence(schedule)
    me = _me(axis_name, node_index)

    def one(sched: PermuteSchedule, v: jax.Array) -> jax.Array:
        total = jnp.zeros_like(v)
        for rnd in sched.rounds:
            recv = _wire_ppermute(v, axis_name, rnd.perm)
            total = total + _round_weight(rnd, me, v.dtype) * recv
        return total

    if seq.length == 1:
        return one(seq.schedules[0], x)
    if step is None:
        raise ValueError("time-varying ScheduleSequence needs step=")
    return jax.lax.switch(step % seq.length,
                          [functools.partial(one, s) for s in seq.schedules],
                          x)


def exchange_payload(schedule, payload, decompress, axis_name, *,
                     step=None, node_index=None) -> jax.Array:
    """Weighted neighbour sum of DECOMPRESSED compressor payloads.

    The generic transport behind ``repro.core.compressor``: ``payload``
    is any shape-static pytree (a ``compressor.Payload`` — values,
    explicit indices, scale scalar), and every leaf crosses the wire
    as-is via one ppermute per schedule round; the receiver runs
    ``decompress(recv_payload)`` and weighs locally. Nothing is
    regenerated from shared seeds, so ANY registered compressor works —
    packed fixed-k with explicit indices, int8 quantized values, dense
    masks — at the cost of shipping the index/scale side-channels
    (``exchange_packed*`` stays the seed-synchronized fast path for the
    SDM fixed-k modes). Non-destination receivers get ppermute's implicit
    zero payloads and a zero weight, so the sum is exact on any graph;
    time-varying sequences index by the traced ``step``.
    """
    seq = ensure_sequence(schedule)
    me = _me(axis_name, node_index)
    template = decompress(payload)   # shares work with the caller's own
    #                                  decompress via CSE; defines shape/dtype

    def one(sched: PermuteSchedule, pl) -> jax.Array:
        total = jnp.zeros_like(template)
        for rnd in sched.rounds:
            recv = jax.tree.map(
                lambda v: _wire_ppermute(v, axis_name, rnd.perm), pl)
            w = _round_weight(rnd, me, total.dtype)
            total = total + w * decompress(recv)
        return total

    if seq.length == 1:
        return one(seq.schedules[0], payload)
    if step is None:
        raise ValueError("time-varying ScheduleSequence needs step=")
    return jax.lax.switch(step % seq.length,
                          [functools.partial(one, s) for s in seq.schedules],
                          payload)


@jax.named_scope("sdm_draw")
def _batched_sender_indices(schedule: PermuteSchedule, me, *,
                            base_key: jax.Array, step: jax.Array,
                            nb: int, kb: int) -> jax.Array:
    """All this-step senders' index lists, drawn as the senders drew them.

    Every shift round of a step exchanges the same leaf, so the draw is
    batched over the R senders: their (R, nb) score keys, each row's top
    kb set ranked by counting (``sparsifier.topk_mask``, vmapped: the
    search is traced once) and compacted to ascending order
    (``sparsifier.mask_indices``). Vmapped PRNG draws equal the scalar
    ones, so row i is bit for bit the list the shift-s sender of round i
    packed its payload by (``_packed_selection``). Returns (n_rounds, kb)
    indices.
    """
    n = schedule.n_nodes
    shifts = jnp.asarray([rnd.shift for rnd in schedule.rounds], jnp.int32)
    senders = jnp.mod(me - shifts, n)
    keys = jax.vmap(lambda j: node_round_key(base_key, j, step))(senders)

    def sender_list(key):
        keep = sparsifier.topk_mask(sparsifier.score_keys(key, nb), kb,
                                    sparsifier.SCORE_BITS)
        return sparsifier.mask_indices(keep, kb)

    _DRAWS["compact"] += len(schedule.rounds)
    return jax.vmap(sender_list)(keys)


def draw_counts() -> dict:
    """The fixed-k keep-set draws traced so far in this process:
    ``{"own_mask": ..., "compact": ...}`` (see ``_DRAWS``).
    Counted when a step is traced, per draw site, so a reading taken
    before and after lowering a step says what that step holds: 0
    ``compact`` on a schedule with no gossip rounds. The dict is live;
    copy it to keep a reading.
    """
    return _DRAWS


def _kept(nb_blocks: int, p, me) -> Tuple[int, "int | jax.Array"]:
    """(kb, kb_me): the payload's kept blocks and this node's own budget.

    With a per-node tuple ``p`` the payload pads to
    kb = max_i ceil(p_i * nb_blocks) and kb_me = ceil(p_me * nb_blocks)
    is traced (``me`` is); with a scalar ``p`` both are kb.
    """
    if isinstance(p, tuple):
        k_table = tuple(sparsifier.num_kept(nb_blocks, pi) for pi in p)
        return max(k_table), jnp.asarray(k_table, jnp.int32)[me]
    kb = sparsifier.num_kept(nb_blocks, p)
    return kb, kb


def _own_and_payload(db: jax.Array, to_leaf, p, me, *,
                     base_key: jax.Array, step: jax.Array
                     ) -> Tuple[jax.Array, int, jax.Array]:
    """(own S(d) in the leaf's shape, kb, packed payload) from the node's
    ONE draw of the round: the score keys of ``node_round_key(base, me,
    step)``, ranked once for the own keep set.

    * The own S(d) is the top kb_me keys — the payload's rows it does not
      zero — found as a mask (``sparsifier.topk_mask``: no sort) and
      applied as a dense select: no gather or scatter of the plane.
      Bit-equal to scattering the payload back: the same rows, each
      times the same scale nb_blocks / kb_me.
    * The payload (``_packed_selection``) is the wire's alone: on a
      schedule with no round it is dead code, which XLA drops with its
      index list.
    """
    nb_blocks = db.shape[0]
    kb, kb_me = _kept(nb_blocks, p, me)
    keys = sparsifier.score_keys(node_round_key(base_key, me, step),
                                 nb_blocks)
    scale = (nb_blocks / kb_me.astype(jnp.float32)
             if isinstance(p, tuple) else nb_blocks / kb_me)
    keep = sparsifier.topk_mask(keys, kb_me, sparsifier.SCORE_BITS)
    _DRAWS["own_mask"] += 1
    # one own plane, written once in the block view's layout: without the
    # barrier XLA copies the select into each leaf's consumer, each with
    # its own copy of the mask in that leaf's layout, and lays leaves out
    # again from a flat copy (on the TPU, 14 GB more HBM traffic a step
    # for a 425 M-coordinate plane of 128-coordinate blocks)
    own = jax.lax.optimization_barrier(
        jnp.where(keep[:, None], (db * scale).astype(db.dtype),
                  jnp.zeros((), db.dtype)))
    return to_leaf(own), kb, _packed_selection(db, keys, keep, kb, kb_me, p)


@jax.named_scope("sdm_pack")
def _packed_selection(db: jax.Array, keys: jax.Array, keep: jax.Array,
                      kb: int, kb_me, p) -> jax.Array:
    """Sender-side packed payload: the (kb, block) rows of ``db`` in the
    top kb set of ``keys``, in ascending row order, scaled.

    The list is a counted mask compacted (``sparsifier.mask_indices``),
    the one receivers regenerate (``_batched_sender_indices``): the set
    of ``fixedk_indices``, with no sort. With a scalar ``p`` the set is
    the own keep set ``keep``, compacted without ranking the keys again.

    The ONE implementation shared by the static (``_packed_exchange``)
    and the replica/union (``_union_packed_exchange``) transports, so
    their bit-equality contract (same keys, same pad-to-max-k payloads)
    cannot desynchronize.

    ``p`` may be a per-node tuple: the payload then pads to
    k_max = max_i ceil(p_i * n_blocks) — every node sends the rows of
    its top k_max keys, zeroes those outside its OWN top k_i (``keep``)
    and scales the rest by n_blocks/k_i. The rows are distinct, so the
    zero pad rows scatter onto coordinates the sender did not select
    (already zero in S(d)) and receivers need no masking: the wire keeps
    ONE static shape while each node transmits its own budget.
    """
    nb_blocks = db.shape[0]
    if isinstance(p, tuple):
        my_idx = sparsifier.mask_indices(
            sparsifier.topk_mask(keys, kb, sparsifier.SCORE_BITS), kb)
        scale = (nb_blocks / kb_me.astype(jnp.float32)) \
            * jnp.take(keep, my_idx)[:, None]
    else:
        my_idx = sparsifier.mask_indices(keep, kb)
        scale = nb_blocks / kb
    return (jnp.take(db, my_idx, axis=0) * scale).astype(db.dtype)


def _unpack(db: jax.Array, vals: jax.Array, idx: jax.Array) -> jax.Array:
    """Scatter a received payload's rows back into a zero block view.

    ``idx`` is a sender's list, ascending and distinct
    (``sparsifier.mask_indices``); told so, the TPU's scatter does not
    sort it first.
    """
    return jnp.zeros_like(db).at[idx].set(vals, indices_are_sorted=True,
                                          unique_indices=True)


def _row_view(d: jax.Array) -> jax.Array:
    """(rows, cols) view of a leaf whose blocks are trailing-dim rows."""
    cols = d.shape[-1] if d.ndim > 1 else 1
    return d.reshape(d.size // cols, cols)


@jax.named_scope("sdm_pack")
def _packed_exchange(seq: ScheduleSequence, db: jax.Array, to_leaf, *,
                     axis_name, base_key: jax.Array, step: jax.Array,
                     p, node_index) -> Tuple[jax.Array, jax.Array]:
    """Shared engine for packed gossip on a (2-D block view of a) leaf.

    ``to_leaf`` reshapes a block view back to the leaf's shape. The own
    S(d) and the payload (``_own_and_payload``) are drawn and packed
    once, OUT of the schedule branches (they depend only on (me, step)),
    so time-varying sequences pay one packing + one switch over nb-sum
    branches. A schedule with no rounds (one node) sends no payload, so
    its step keeps no index list.
    """
    nb_blocks = db.shape[0]
    me = _me(axis_name, node_index)
    own_sparse, kb, my_vals = _own_and_payload(
        db, to_leaf, p, me, base_key=base_key, step=step)
    # the payload's index list is left in the built step only where a
    # round sends it
    _DRAWS["compact"] += any(sched.rounds for sched in seq.schedules)

    def nb_for(sched: PermuteSchedule, vals_out: jax.Array) -> jax.Array:
        nb_sum = jnp.zeros_like(own_sparse)
        if not sched.rounds:
            return nb_sum
        sender_idx = _batched_sender_indices(
            sched, me, base_key=base_key, step=step, nb=nb_blocks, kb=kb)
        for i, rnd in enumerate(sched.rounds):
            # Wire traffic: only the packed (kb, block) values move.
            vals = _wire_ppermute(vals_out, axis_name, rnd.perm)
            w = _round_weight(rnd, me, own_sparse.dtype)
            nb_sum = nb_sum + w * to_leaf(_unpack(db, vals, sender_idx[i]))
        return nb_sum

    if seq.length == 1:
        return own_sparse, nb_for(seq.schedules[0], my_vals)
    return own_sparse, jax.lax.switch(
        step % seq.length,
        [functools.partial(nb_for, s) for s in seq.schedules], my_vals)


def exchange_packed(schedule, d_flat: jax.Array, *,
                    axis_name, base_key: jax.Array, step: jax.Array,
                    p, block: int = 1,
                    node_index=None) -> Tuple[jax.Array, jax.Array]:
    """One packed gossip round on any schedule; returns (own_sparse, nb_sum).

    Per round s only the sender's packed (kb, block) values cross the
    wire; the receiver regenerates the shift-s sender's index set from
    ``node_round_key(base_key, (me - s) % n, step)`` (one batched draw
    per step shared across rounds) and scatters + weighs locally.
    ``nb_sum = sum_{j in N_i} W_ij S(d_j)`` densified. Accepts a
    time-varying ScheduleSequence (round picked by ``step``).
    """
    dim = d_flat.shape[0]
    return _packed_exchange(
        ensure_sequence(schedule), sparsifier.block_view(d_flat, block),
        lambda rows: rows.reshape(-1)[:dim], axis_name=axis_name,
        base_key=base_key, step=step, p=p, node_index=node_index)


def exchange_packed_rows(schedule, d: jax.Array, *,
                         axis_name, base_key: jax.Array, step: jax.Array,
                         p,
                         node_index=None) -> Tuple[jax.Array, jax.Array]:
    """Sharding-aligned packed gossip on any schedule (blocks = rows).

    The block unit is a whole trailing-dim row: the gather indexes only
    the unsharded leading dims, so each packed row — and the ppermute
    payload — keeps the leaf's model-axis sharding (flattening the leaf
    would make GSPMD all-gather it around the gather/scatter). Selection
    semantics equal ``sparsifier.block_sparsify`` with
    block = leaf.shape[-1] (row-major): inclusion probability k/rows
    ~= p, scale rows/k. Generalized to every schedule round and to
    time-varying sequences.
    """
    return _packed_exchange(
        ensure_sequence(schedule), _row_view(d),
        lambda rows: rows.reshape(d.shape), axis_name=axis_name,
        base_key=base_key, step=step, p=p, node_index=node_index)


# --------------------------------------------------------------------------
# Distributed ring path (inside shard_map, node axis manual).
# --------------------------------------------------------------------------

def _perm(n: int, shift: int) -> Sequence[Tuple[int, int]]:
    return [(i, (i + shift) % n) for i in range(n)]


def ring_exchange(x, axis_name) -> Tuple[jax.Array, jax.Array]:
    """Send ``x`` to both ring neighbours; returns (from_left, from_right).

    ``from_left[i] = x[i-1]`` and ``from_right[i] = x[i+1]``.
    """
    n = jax.lax.psum(1, axis_name)
    from_left = _wire_ppermute(x, axis_name, _perm(n, +1))
    from_right = _wire_ppermute(x, axis_name, _perm(n, -1))
    return from_left, from_right


def ring_weighted_neighbor_sum(x, axis_name, neighbor_weight: float) -> jax.Array:
    """sum_{j in N_i} W_ij x_j for the symmetric ring (both neighbours weight w)."""
    from_left, from_right = ring_exchange(x, axis_name)
    return neighbor_weight * (from_left + from_right)


# --------------------------------------------------------------------------
# Seed-synchronised keys.
# --------------------------------------------------------------------------

def node_round_key(base_key: jax.Array, node_index, step) -> jax.Array:
    """Sparsifier seed both endpoints can regenerate: f(base, node, round)."""
    return jax.random.fold_in(jax.random.fold_in(base_key, node_index), step)
