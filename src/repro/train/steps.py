"""Production train / prefill / decode step factories.

``make_distributed_train`` assembles the paper's algorithm at pod scale:

  * the node axis (``('pod','data')`` flattened) is MANUAL under
    `jax.shard_map` — each shard-group is one SDM-DSGD edge node running
    ring gossip with `lax.ppermute` (collective-permute on ICI);
  * the ``model`` axis stays AUTO — GSPMD tensor-partitions each node's
    model from the logical sharding rules;
  * per-node gradient -> coordinate clip -> Gaussian mask -> generalized
    theta-mixing -> sparse differential exchange, exactly Algorithm 1.

The per-node algorithm is METHOD-GENERIC: ``DistributedTrainConfig.method``
names a ``repro.core.method`` registry entry (sdm-dsgd, sdm-dsgd-fused,
dc-dsgd, dsgd, gradient-push, allreduce, ...), and this factory runs its
shard_map distributed executor — all methods share the same factory so
the roofline benchmarks compare like-for-like, and adding a method means
registering it, not editing this file.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import gossip, method as method_mod, plane as plane_mod
from repro.core import tagging
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.sharding import MeshRules, use_rules

PyTree = Any

# Logical-axis -> mesh-axis mapping used INSIDE the node-manual shard_map
# (node axes are manual there, so only 'model' appears) ...
INNER_RULES: Mapping[str, Any] = {
    "heads": "model", "kv_heads": "model", "mlp": "model",
    "heads_flat": "model", "kv_flat": "model",
    "vocab": "model", "experts": "model",
    "batch": None, "seq": None, "embed": None, "layers": None,
    "cache_seq": None,
}


def outer_rules(node_axes: Tuple[str, ...]) -> dict:
    """Rules for plain-jit (serving) steps and for jit-level in_shardings."""
    rules = dict(INNER_RULES)
    rules["batch"] = node_axes if len(node_axes) > 1 else node_axes[0]
    return rules


def serving_rules(node_axes: Tuple[str, ...], *, shard_cache_seq: bool,
                  decode: bool = False) -> dict:
    rules = outer_rules(node_axes)
    if decode:
        # flash-decoding layout: the KV cache's sequence dim shards over
        # the model axis (idle during decode attention); softmax over the
        # sharded length costs only tiny max/sum psums per layer.
        rules["cache_seq"] = "model"
    if shard_cache_seq:
        # long-context decode: batch=1 cannot shard; spread the cache's
        # sequence dim over BOTH data and model axes instead.
        rules["cache_seq"] = ("data", "model")
        rules["batch"] = None
    return rules


@dataclasses.dataclass(frozen=True)
class DistributedTrainConfig:
    """Production train-step configuration.

    ``method`` names a ``repro.core.method`` registry entry (legacy
    underscore spellings like "sdm_dsgd" normalize transparently).
    ``sdm`` is the hyper-parameter bag; each method coerces it to its
    own config dataclass (e.g. DSGD keeps only gamma/sigma/clip_c).
    """

    model: ModelConfig
    sdm: Any
    topology: str = "ring"              # spec for gossip.sequence_by_name
    topology_seed: int = 0              # ER graph / matching sampling seed
    self_weight: float = 1.0 / 3.0      # ring W_ii; neighbours get (1-W_ii)/2
    method: str = "sdm-dsgd"            # method registry name
    param_dtype: Any = jnp.bfloat16

    def resolved(self):
        """(Method, method-native config) for this run."""
        meth = method_mod.get(self.method)
        return meth, meth.coerce_config(self.sdm)


def _node_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


def _n_nodes(mesh: Mesh) -> int:
    n = 1
    for a in _node_axes(mesh):
        n *= mesh.shape[a]
    return n


@functools.lru_cache(maxsize=None)
def _compiled_schedule(spec: str, seed: int, self_weight: float,
                       n_nodes: int) -> gossip.ScheduleSequence:
    return gossip.sequence_by_name(
        spec, n_nodes,
        self_weight=self_weight if spec == "ring" else None, seed=seed,
        placement=True)


def gossip_schedule(tc: DistributedTrainConfig, mesh: Mesh
                    ) -> gossip.ScheduleSequence:
    """Compile the configured gossip graph for this mesh's node count.

    Memoized: the launcher banner, init_distributed_state, and
    make_distributed_train all resolve to the SAME schedule object, so
    ER resampling + the Laplacian eigendecomposition run once and the
    s_0 self-weights can never desynchronize from the train step's.
    Time-varying specs ("matchings:<L>") give a length-L sequence.

    Placement-aware: the node count is read off the mesh's ICI shape and
    ``topology.greedy_placement`` renumbers the logical nodes before
    compiling whenever that strictly lowers the ring-hop cost, so e.g.
    a sampled ER graph's hottest shifts land on physically adjacent
    devices. Spectrum-preserving — beta / lambda_n and every convergence
    bound are untouched (asserted in tests/test_core_topology.py).
    """
    return _compiled_schedule(tc.topology, tc.topology_seed,
                              tc.self_weight, _n_nodes(mesh))


def plane_bucket_tree(tc: DistributedTrainConfig, mesh: Mesh):
    """The wire-plane bucket policy for this run (this file owns it).

    On a tensor-parallel mesh, leaves whose TRAILING logical axis maps
    to the model axis get their own plane bucket keyed ``('model',
    cols)`` — the plane's lane dim keeps the TP sharding
    (DDP-gradient-bucket style); everything else rides the default flat
    bucket. On meshes without a model axis one flat plane is optimal:
    return None.
    """
    if "model" not in mesh.shape or mesh.shape["model"] == 1:
        return None
    return plane_mod.bucket_keys_from_axes(
        transformer.param_axes(tc.model), transformer.param_shapes(tc.model),
        INNER_RULES)


def _bucket_ctx(tc: DistributedTrainConfig, mesh: Mesh):
    return plane_mod.use_buckets(plane_bucket_tree(tc, mesh))


def state_shape_dtype(tc: DistributedTrainConfig, mesh: Mesh):
    """ShapeDtypeStructs of the stacked method state (dry-run lowering).

    Schedule-aware: genuinely time-varying gossip specs grow the
    per-neighbour REPLICA leaves (one slot per union-graph round).
    """
    n_nodes = _n_nodes(mesh)
    meth, mcfg = tc.resolved()
    shapes = transformer.param_shapes(tc.model)
    mk = lambda s: jax.ShapeDtypeStruct((n_nodes,) + tuple(s), tc.param_dtype)
    x = jax.tree.map(mk, shapes,
                     is_leaf=lambda v: isinstance(v, tuple) and
                     all(isinstance(e, int) for e in v))
    with _bucket_ctx(tc, mesh):
        return method_mod.state_shape_dtype(meth, x, mcfg,
                                            seq=gossip_schedule(tc, mesh))


def state_shardings(tc: DistributedTrainConfig, mesh: Mesh):
    """NamedShardings for the stacked distributed state."""
    node_axes = _node_axes(mesh)
    meth, mcfg = tc.resolved()
    rules = MeshRules(mesh, outer_rules(node_axes))
    axes = transformer.param_axes(tc.model)
    shapes = transformer.param_shapes(tc.model)
    is_axes = lambda v: isinstance(v, tuple) and all(
        isinstance(e, (str, type(None))) for e in v)

    def leaf_sharding(a, s):
        return rules.sharding(("batch",) + a, (0,) + tuple(s))

    x = jax.tree.map(leaf_sharding, axes, shapes, is_leaf=is_axes)
    node_vec = NamedSharding(mesh, P(node_axes if len(node_axes) > 1
                                     else node_axes[0]))
    n_nodes = _n_nodes(mesh)
    is_shape = lambda v: isinstance(v, tuple) and all(
        isinstance(e, int) for e in v)
    template = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_nodes,) + tuple(s),
                                       tc.param_dtype),
        shapes, is_leaf=is_shape)
    with _bucket_ctx(tc, mesh):
        return method_mod.state_shardings(meth, x, node_vec, mcfg,
                                          seq=gossip_schedule(tc, mesh),
                                          template=template)


def init_distributed_state(tc: DistributedTrainConfig, mesh: Mesh,
                           key: jax.Array):
    """Materialize the stacked state (same init on every node).

    Method-generic: e.g. SDM's s_0[i] = (1 - W_ii(0)) x_0 with the
    node's OWN self-weight (W_ii varies per node on Metropolis–Hastings
    graphs), gradient-push's mass w_0 = 1. Built under one jit whose
    outputs carry ``state_shardings``, so each device materializes only
    its own node's slice — the stacked n-node state never exists on one
    device.
    """
    n_nodes = _n_nodes(mesh)
    meth, cfg = tc.resolved()
    seq = gossip_schedule(tc, mesh)

    def build(key):
        params = transformer.init_params(key, tc.model, tc.param_dtype)
        stack = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (n_nodes,) + p.shape),
            params)
        with _bucket_ctx(tc, mesh):
            return meth.init_stacked(stack, seq, cfg)

    return jax.jit(build, out_shardings=state_shardings(tc, mesh))(key)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Token batches: the leading (global batch) dim split over the nodes."""
    node_axes = _node_axes(mesh)
    return NamedSharding(mesh, P(node_axes if len(node_axes) > 1
                                 else node_axes[0]))


def make_distributed_train(tc: DistributedTrainConfig, mesh: Mesh,
                           base_key: Optional[jax.Array] = None
                           ) -> Callable:
    """Returns train_step(state, tokens, labels[, context]) -> (state, loss),
    or (state, loss, moe_rows) for a model with MoE layers: the rows
    routed to the experts each node holds, summed over layers and nodes.

    tokens/labels: (global_batch, seq) sharded over the node axes.
    """
    cfg = tc.model
    node_axes = _node_axes(mesh)
    axis = node_axes if len(node_axes) > 1 else node_axes[0]
    inner = MeshRules(mesh, INNER_RULES)
    meth, mcfg = tc.resolved()
    seq = gossip_schedule(tc, mesh)
    if getattr(mcfg, "overlap", False) and gossip.needs_replicas(seq):
        # fail at build time with the run's own topology spec, not deep
        # inside the executor: the double-buffered overlap transport has
        # no replica (time-varying) delivery path.
        raise ValueError(
            f"overlap=True needs a static topology; {tc.topology!r} "
            f"compiles to a replica (time-varying) schedule")
    executor = meth.make_distributed(seq, mcfg, axis)
    if base_key is None:
        base_key = jax.random.PRNGKey(0)

    def local_grads(params, tokens, labels, context):
        def loss_fn(p):
            # the backward pass keeps the scope: transpose(jvp(model_fwd))
            with jax.named_scope("model_fwd"):
                logits, aux, rows = transformer.forward_and_rows(
                    p, cfg, tokens, context=context)
                return transformer.lm_loss(logits, labels, cfg.vocab_size,
                                           aux), rows

        (loss, rows), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        return grads, ((loss, rows) if cfg.has_moe else loss)

    def node_step(state, tokens, labels, context, node_ids):
        """Per-node body; runs under shard_map with `axis` manual.

        state leaves arrive as (1, ...) (node-stacked, one per shard group);
        tokens/labels/context arrive as the node's local batch slice.
        node_ids arrives as the node's (1,)-slice of arange(n_nodes) — the
        node index as DATA, because `axis_index` cannot lower in
        partial-auto shard_map on older jaxlibs (PartitionId).
        """
        squeeze = lambda t: jax.tree.map(lambda v: jnp.squeeze(v, 0), t)
        me = jnp.squeeze(node_ids, 0)

        # bucket keys are static trace-time metadata: the SAME policy the
        # state templates above were built under, so the executor's plane
        # layout cannot diverge from the state it receives.
        with use_rules(inner), _bucket_ctx(tc, mesh):
            state = squeeze(state)
            state, out = executor.step(
                state,
                lambda p: local_grads(p, tokens, labels, context),
                base_key=base_key, node_index=me)

        # the training loss IS data-derived; averaging it over nodes is a
        # deliberate release (the metric), declared so the taint auditor
        # reports it instead of flagging the psum. So is the count of
        # rows the router sent to this node's experts.
        loss, rows = out if cfg.has_moe else (out, None)
        loss = jax.lax.pmean(tagging.declared_release(loss, label="loss"),
                             axis)
        unsqueeze = lambda t: jax.tree.map(lambda v: v[None], t)
        if not cfg.has_moe:
            return unsqueeze(state), loss
        rows = jax.lax.psum(tagging.declared_release(rows, label="moe_rows"),
                            axis)
        return unsqueeze(state), loss, rows

    state_specs = jax.tree.map(lambda _: P(axis), state_shape_dtype(tc, mesh))
    data_spec = P(axis)

    has_context = cfg.family in ("audio", "vlm")
    in_specs = (state_specs, data_spec, data_spec,
                data_spec if has_context else None, P(axis))
    node_ids = jnp.arange(_n_nodes(mesh), dtype=jnp.int32)

    def train_step(state, tokens, labels, context=None):
        fn = jax.shard_map(
            node_step, mesh=mesh,
            in_specs=in_specs,
            out_specs=(state_specs, P()) + ((P(),) if cfg.has_moe else ()),
            axis_names=set(node_axes), check_vma=False)
        return fn(state, tokens, labels, context, node_ids)

    return train_step


# --------------------------------------------------------------------------
# Serving steps (plain GSPMD; no node semantics)
# --------------------------------------------------------------------------

def make_prefill_fn(cfg: ModelConfig, mesh: Mesh, *,
                    shard_cache_seq: bool = False,
                    rule_overrides=None) -> Callable:
    node_axes = _node_axes(mesh)
    rules_map = serving_rules(node_axes, shard_cache_seq=shard_cache_seq,
                              decode=False)
    rules_map.update(rule_overrides or {})
    rules = MeshRules(mesh, rules_map)

    def prefill_step(params, tokens, cache, context=None):
        with use_rules(rules):
            return transformer.prefill(params, cfg, tokens, cache,
                                       context=context)

    return prefill_step, rules


def make_decode_fn(cfg: ModelConfig, mesh: Mesh, *,
                   shard_cache_seq: bool = False,
                   rule_overrides=None) -> Callable:
    node_axes = _node_axes(mesh)
    rules_map = serving_rules(node_axes, shard_cache_seq=shard_cache_seq,
                              decode=True)
    rules_map.update(rule_overrides or {})
    rules = MeshRules(mesh, rules_map)

    def decode_fn(params, token, cache, context=None):
        with use_rules(rules):
            return transformer.decode_step(params, cfg, token, cache,
                                           context=context)

    return decode_fn, rules
