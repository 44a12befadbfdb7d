"""Roofline share of the fused fixed-k pack kernel
(``kernels.wire_compress.fixedk_gather_pack``): the bytes of its kept
rows read and written plus their indices (``bench.flops.fixedk_pack``)
over HBM bandwidth, against the kernel's mean device time per call.
Memory-bound. Silent where the step has no such kernel."""
from bench import peaks

UNIT = "%"
KERNEL = "fixedk_gather_pack"


def read(rec, trace):
    if rec["kind"] != "train" or not rec["counters"].get("pack"):
        return None
    calls = secs = 0
    for d in trace["devices"]:
        for name, (n, s) in d["ops"].items():
            if KERNEL in name:
                calls, secs = calls + n, secs + s
    if not calls:
        return None
    bw = peaks.peak(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * rec["counters"]["pack"]["bytes"] / bw / (secs / calls)
