"""Subprocess body for tests/test_analysis.py: trace the deliberately
MIScalibrated fixture method on a 4-node fake host mesh and run ALL the
analysis passes on it. Prints one JSON object on stdout.

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

from fixtures.miscalibrated_method import miscalibrated_step  # noqa: E402
from repro.analysis import (calibration, jaxpr_taint,  # noqa: E402
                            prng_lint, sensitivity)
from repro.core import gossip, topology  # noqa: E402

N, DIM, BATCH = 4, 64, 8
SIGMA, CLIP_C = 1.0, 1.0


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
            out = miscalibrated_step(
                x, a, b, axis_name="data", schedule=seq,
                base_key=base_key, step=jnp.int32(0),
                sigma=SIGMA, clip_c=CLIP_C)
            return out[None]

        return jax.shard_map(inner, mesh=mesh,
                             in_specs=(P("data"), P("data"), P("data")),
                             out_specs=P("data"),
                             axis_names={"data"},
                             check_vma=False)(x_st, a_st, b_st)

    jaxpr = jax.make_jaxpr(dist)(x_st, a_st, b_st)
    taint = jaxpr_taint.analyze_taint(jaxpr, {1: "data", 2: "data"})
    prng = prng_lint.analyze_prng(jaxpr)
    sens = sensitivity.analyze_sensitivity(
        jaxpr, {1: "data", 2: "data"}, clip_c=CLIP_C)
    calib = calibration.analyze_calibration(
        jaxpr, expected_sigma=SIGMA, expected_clip=CLIP_C)
    ovl = calibration.analyze_overlap(jaxpr, overlap=False)
    print(json.dumps({
        "taint": taint["findings"],
        "prng": prng["findings"],
        "sensitivity": sens["findings"],
        "calibration": calib["findings"],
        "overlap": ovl["findings"],
        "sanitize_bounds": sens["sanitize_sites"],
        "extracted_noise": calib["sanitize_sites"],
        "clip_sites": sens["clip_sites"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
