"""Subprocess helper: a node's own S(d) and what it receives from the
packed transports, on a 4-node CPU mesh, against dense expectations
built from ``fixedk_indices`` and a scatter — the own S(d) is the
node's payload scattered back, the form the own side had before it
became a dense select; the neighbour sum (or the replica transport's
per-slot increments) is the senders' payloads scattered back and
weighed — and the draw counter of a 4-node train step.

Run with 4 fake host devices; prints one line per case

    CASE <id> EQUAL <0|1> NONZERO <i> NB_EQUAL <0|1> NB_NONZERO <i>
    RANDOM <id> <i>
    DRAWS own_mask <i> compact <i> SORT <i>

that tests/test_own_sdm_select.py asserts on. Must set XLA_FLAGS before
the jax import.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, PartitionSpec as P  # noqa: E402

from repro.core import gossip, sparsifier  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402

N = 4
BASE_KEY = jax.random.PRNGKey(7)
STEP = 3
HET_P = (0.1, 0.35, 0.5, 0.2)

# id -> (transport, leaf shape, dtype, p, block); "union" runs the
# replica transport of a time-varying matchings sequence
CASES = {
    "packed_b1": ("static", (1000,), jnp.float32, 0.3, 1),
    "packed_b1_bf16": ("static", (1000,), jnp.bfloat16, 0.3, 1),
    "packed_b128": ("static", (128 * 9 + 17,), jnp.float32, 0.25, 128),
    "rows": ("static_rows", (37, 24), jnp.float32, 0.3, None),
    "hetp_rows": ("static_rows", (37, 24), jnp.float32, HET_P, None),
    "hetp_b1": ("static", (1000,), jnp.float32, HET_P, 1),
    "hetp_b128": ("static", (128 * 9 + 17,), jnp.float32, HET_P, 128),
    "union_b1": ("union", (1000,), jnp.float32, 0.3, 1),
    "union_hetp_b128": ("union", (128 * 9 + 17,), jnp.float32, HET_P, 128),
    "union_rows": ("union_rows", (37, 24), jnp.float32, 0.3, None),
}


def scattered_own(d, p, me, block):
    """S(d) as the payload scattered back into a zero block view."""
    if block is None:
        cols = d.shape[-1]
        db = d.reshape(-1, cols)
        to_leaf = lambda rows: rows.reshape(d.shape)
    else:
        db = sparsifier.block_view(d, block)
        to_leaf = lambda rows: rows.reshape(-1)[:d.shape[0]]
    nb = db.shape[0]
    if isinstance(p, tuple):
        k_table = [sparsifier.num_kept(nb, pi) for pi in p]
        kb, kb_me = max(k_table), k_table[me]
        scale = (nb / jnp.float32(kb_me)) * (jnp.arange(kb)[:, None] < kb_me)
    else:
        kb = sparsifier.num_kept(nb, p)
        scale = nb / kb
    idx = sparsifier.fixedk_indices(
        gossip.node_round_key(BASE_KEY, me, STEP), nb, kb)
    vals = (jnp.take(db, idx, axis=0) * scale).astype(db.dtype)
    return to_leaf(jnp.zeros_like(db).at[idx].set(vals))


RING = gossip.sequence_by_name("ring", N)
UNION = gossip.union_schedule(gossip.sequence_by_name("matchings:3", N))


def own_of(mesh, name):
    """(the case's leaves on N nodes, the jitted transport returning each
    node's own S(d) and its neighbour sum or per-slot increments)."""
    transport, shape, dtype, p, block = CASES[name]
    d = jax.random.normal(jax.random.PRNGKey(1), (N,) + shape, jnp.float32
                          ).astype(dtype)
    kw = dict(axis_name="data", base_key=BASE_KEY, step=jnp.int32(STEP),
              p=p)

    def body(dl):
        dl = dl[0]
        if transport == "static":
            own, nb = gossip.exchange_packed(RING, dl, block=block, **kw)
        elif transport == "static_rows":
            own, nb = gossip.exchange_packed_rows(RING, dl, **kw)
        elif transport == "union":
            own, nb = gossip.union_exchange_packed(UNION, dl, block=block,
                                                   **kw)
        else:
            own, nb = gossip.union_exchange_packed_rows(UNION, dl, **kw)
        return own[None], nb[None]

    return d, jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                    out_specs=P("data"), check_vma=False))


def received(d, name, me):
    """What node ``me`` receives, from the senders' payloads scattered
    back: the weighted sum over the ring's rounds, in round order, or
    one row per union round (zeros where the round has no edge into
    ``me``)."""
    transport, _, dtype, p, block = CASES[name]
    sched = RING.schedules[0] if transport.startswith("static") else UNION
    rows = []
    for rnd in sched.rounds:
        sender = (me - rnd.shift) % N
        if (sender, me) in rnd.perm:
            rows.append(scattered_own(d[sender], p, sender, block))
        else:
            rows.append(jnp.zeros_like(d[me]))
    if transport.startswith("union"):
        return jnp.stack(rows)
    total = jnp.zeros_like(d[me])
    for rnd, row in zip(sched.rounds, rows):
        w = jnp.asarray(rnd.recv_weights, jnp.float32)[me].astype(dtype)
        total = total + w * row
    return total


def run_case(mesh, name):
    _, _, _, p, block = CASES[name]
    d, exchange = own_of(mesh, name)
    own, nb = (np.asarray(v.astype(jnp.float32)) for v in exchange(d))
    want = np.stack([np.asarray(scattered_own(d[i], p, i, block)
                                .astype(jnp.float32)) for i in range(N)])
    # jitted as the transport is: XLA's CPU backend fuses the weighted
    # sum's multiply-adds, which rounds differently from eager ops
    expect = jax.jit(received, static_argnums=(1, 2))
    want_nb = np.stack([np.asarray(expect(d, name, i).astype(jnp.float32))
                        for i in range(N)])
    print(f"CASE {name} EQUAL {int(np.array_equal(own, want))} "
          f"NONZERO {int((own != 0).sum())} "
          f"NB_EQUAL {int(np.array_equal(nb, want_nb))} "
          f"NB_NONZERO {int((nb != 0).sum())}", flush=True)


def random_draws(mesh, name):
    """The random-bits draws one node's exchange makes (PRNG lint count):
    its own keys, drawn once for its own S(d) and its payload, and its
    senders' batched keys."""
    from repro.analysis import prng_lint

    d, exchange = own_of(mesh, name)
    n = prng_lint.analyze_prng(jax.make_jaxpr(exchange)(d))["n_draws"]
    print(f"RANDOM {name} {n}", flush=True)


def step_draws():
    """Draws the 4-node fixed-k train step holds, and its sort and top_k
    ops: one own mask, one compacted payload list and one compacted list
    per sender a plane."""
    from repro import configs
    from repro.core.sdm_dsgd import SDMConfig
    from repro.train import steps

    mesh = jax.make_mesh((N,), ("data",), axis_types=(AxisType.Auto,))
    tc = steps.DistributedTrainConfig(
        model=configs.get_smoke_config("chatglm3-6b"),
        sdm=SDMConfig(p=0.2, theta=0.5, gamma=0.01, sigma=0.5, clip_c=1.0,
                      mode="fixedk_packed"),
        topology="ring", method="sdm-dsgd", param_dtype=jnp.float32)
    toks = jax.ShapeDtypeStruct((N, 16), jnp.int32)
    before = dict(gossip.draw_counts())
    lowered = jax.jit(steps.make_distributed_train(tc, mesh)).lower(
        steps.state_shape_dtype(tc, mesh), toks, toks)
    after = gossip.draw_counts()
    hlo = lowered.compile().as_text()
    # XLA's CPU backend lowers top_k to a TopK custom call
    sorts = (hlo_analysis.instruction_counts(hlo).get("sort", 0)
             + hlo.count('custom_call_target="TopK"'))
    print("DRAWS " + " ".join(f"{k} {after[k] - before[k]}" for k in after)
          + f" SORT {sorts}", flush=True)


def main():
    mesh = jax.make_mesh((N,), ("data",), axis_types=(AxisType.Auto,))
    for name in CASES:
        run_case(mesh, name)
    for name in ("packed_b128", "union_b1"):
        random_draws(mesh, name)
    step_draws()


if __name__ == "__main__":
    main()
