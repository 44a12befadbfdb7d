"""Model FLOP utilisation of the training step: forward and backward
model FLOPs per token (``bench.flops``) times the window's tokens per
second, over chips times the chip's bf16 peak. Recomputation and the
SDM element-wise work are not counted."""
from bench import peaks

UNIT = "%"


def read(rec, trace):
    if rec["kind"] != "train":
        return None
    c = rec["counters"]
    rate = c["tokens"] / rec["window_s"]
    peak = peaks.peak(rec["device_kind"])["bf16_flops"]
    return 100.0 * c["flops_per_token"] * rate / (c["chips"] * peak)
