"""Device seconds per training step in the held experts' grouped matmuls
(span ``moe_experts`` and XLA's ``ragged-dot`` kernels), forward,
backward and recomputed, mean over chips (``bench.moe_spans``). Silent
where the model has no expert layers."""
from bench import moe_spans

UNIT = "s"


def read(rec, trace):
    return moe_spans.read_span(rec, trace, "moe_experts")
