"""Device seconds per training step in the sort operations that draw the
fixed-k index sets (the sender's own and its neighbours'; XLA lowers
``lax.top_k`` to a sort on the TPU), mean over chips."""
UNIT = "s"


def read(rec, trace):
    if rec["kind"] != "train":
        return None
    secs = [d["categories"].get("sort", 0.0) for d in trace["devices"]]
    if not any(secs):
        return None
    return sum(secs) / len(secs) / rec["counters"]["steps"]
