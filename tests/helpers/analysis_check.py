"""Subprocess body for tests/test_analysis.py: trace the deliberately
broken fixture method on a 4-node fake host mesh and run the taint and
PRNG passes on it. Prints one JSON object on stdout.

Must run in its own process: the device-count fake below has to land
before jax initializes.
"""
import json
import os
import pathlib
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType, PartitionSpec as P  # noqa: E402

from fixtures.broken_method import broken_step  # noqa: E402
from repro.analysis import jaxpr_taint, prng_lint  # noqa: E402
from repro.core import gossip, topology  # noqa: E402

N, DIM, BATCH = 4, 64, 8


def main() -> int:
    seq = gossip.ensure_sequence(
        gossip.schedule_from_topology(topology.ring(N)))
    rng = np.random.default_rng(0)
    x_st = jnp.asarray(rng.normal(size=(N, DIM)), jnp.float32)
    a_st = jnp.asarray(rng.normal(size=(N, BATCH, DIM)), jnp.float32)
    b_st = jnp.asarray(rng.normal(size=(N, BATCH)), jnp.float32)
    base_key = jax.random.PRNGKey(7)
    mesh = jax.make_mesh((N,), ("data",), axis_types=(AxisType.Auto,))

    def dist(x_st, a_st, b_st):
        def inner(x, a, b):
            x, a, b = (jnp.squeeze(v, 0) for v in (x, a, b))
            out = broken_step(x, a, b, axis_name="data", schedule=seq,
                              base_key=base_key, step=jnp.int32(0))
            return out[None]

        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P("data"), P("data"), P("data")),
                             out_specs=P("data"),
                             axis_names={"data"},
                             check_vma=False)(x_st, a_st, b_st)

    jaxpr = jax.make_jaxpr(dist)(x_st, a_st, b_st)
    taint = jaxpr_taint.analyze_taint(jaxpr, {1: "data", 2: "data"})
    prng = prng_lint.analyze_prng(jaxpr)
    print(json.dumps({
        "taint": taint["findings"],
        "releases": taint["releases"],
        "n_sanitize_sites": taint["n_sanitize_sites"],
        "prng": prng["findings"],
        "n_draws": prng["n_draws"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
