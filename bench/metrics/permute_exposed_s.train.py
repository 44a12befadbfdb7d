"""Device seconds per training step spent in collective-permutes while
no other operation ran on that device, mean over chips. Silent where
the step has no permutes (a single node)."""
UNIT = "s"


def read(rec, trace):
    if rec["kind"] != "train":
        return None
    devs = trace["devices"]
    if not any(d["permute_s"] for d in devs):
        return None
    return sum(d["permute_exposed_s"] for d in devs) / len(devs) \
        / rec["counters"]["steps"]
