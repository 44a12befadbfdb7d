"""Device seconds per training step routing the expert layers' tokens
(span ``moe_route``: router matmul, sigmoid, biased top-k and weights,
the sort into expert order, the rows' gather and the weighted combine
back), forward, backward and recomputed, mean over chips
(``bench.moe_spans``). Silent where the model has no expert layers."""
from bench import moe_spans

UNIT = "s"


def read(rec, trace):
    return moe_spans.read_span(rec, trace, "moe_route")
