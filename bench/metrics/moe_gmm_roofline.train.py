"""Roofline share of the held experts' grouped matmuls: the window's rows
routed to held experts (``counters["moe_rows"]``) times a row's FLOPs in
the three projections forward and backward (``bench.mla_moe_flops``),
per chip, over the bf16 peak, against the device time of span
``moe_experts`` (``bench.moe_spans``). Compute-bound at the cells'
shapes. The backward pass's recomputed forward is in that time and not
in the FLOPs. Silent where the model has no expert layers."""
from bench import moe_spans, peaks

UNIT = "%"


def read(rec, trace):
    secs = moe_spans.read_span(rec, trace, "moe_experts")
    if secs is None:
        return None
    c = rec["counters"]
    flops = c["moe_rows"] * c["gmm_flops_per_row"] / c["chips"]
    peak = peaks.peak(rec["device_kind"])["bf16_flops"]
    return 100.0 * flops / (secs * c["steps"]) / peak
