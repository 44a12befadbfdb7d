"""Device seconds per training step in the latent-attention sublayers
(span ``mla_attn``: norm, projections, the latent's norm and
up-projection, RoPE, attention, output projection), forward, backward
and recomputed, mean over chips (``bench.moe_spans``). Silent where the
model has no expert layers."""
from bench import moe_spans

UNIT = "s"


def read(rec, trace):
    return moe_spans.read_span(rec, trace, "mla_attn")
