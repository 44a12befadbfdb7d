"""Subprocess helper: one 4-node CPU SDM-DSGD train step traced under a
wire format's legacy ``mode=`` spelling and under its ``compressor=``
spelling. Prints one line per format,

    SPELLING <spec> <sha256 of the legacy jaxpr> <sha256 of the spec jaxpr>

that tests/test_compressor.py asserts on. Must set XLA_FLAGS before the
jax import.
"""
import hashlib
import os
import re

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.sdm_dsgd import SDMConfig  # noqa: E402
from repro.train import steps  # noqa: E402

N = 4
# (compressor spec, its legacy spelling)
SPELLINGS = [
    ("bernoulli", {"mode": "bernoulli"}),
    ("fixedk", {"mode": "fixedk_packed"}),
    ("block:128", {"mode": "fixedk_packed", "pack_block": 128}),
    ("rows", {"mode": "fixedk_rows"}),
    ("qsgd:8", {"mode": "qsgd"}),
]
KW = dict(p=0.25, theta=0.5, gamma=0.01, sigma=0.5, clip_c=1.0)


def step_jaxpr(mesh, sdm: SDMConfig) -> str:
    tc = steps.DistributedTrainConfig(
        model=configs.get_smoke_config("chatglm3-6b"), sdm=sdm,
        topology="ring", method="sdm-dsgd", param_dtype=jnp.float32)
    toks = jax.ShapeDtypeStruct((N, 16), jnp.int32)
    text = str(jax.make_jaxpr(steps.make_distributed_train(tc, mesh))(
        steps.state_shape_dtype(tc, mesh), toks, toks))
    # the printed closures carry their object addresses
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return hashlib.sha256(text.encode()).hexdigest()


def main():
    mesh = jax.make_mesh((N,), ("data",), axis_types=(AxisType.Auto,))
    for spec, legacy in SPELLINGS:
        a = step_jaxpr(mesh, SDMConfig(**legacy, **KW))
        b = step_jaxpr(mesh, SDMConfig(compressor=spec, **KW))
        print(f"SPELLING {spec} {a} {b}", flush=True)


if __name__ == "__main__":
    main()
