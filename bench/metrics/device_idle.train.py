"""Share of the traced training window in which no operation ran on the
device (1 - union of operation intervals / window), mean over chips."""
UNIT = "%"


def read(rec, trace):
    if rec["kind"] != "train":
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
