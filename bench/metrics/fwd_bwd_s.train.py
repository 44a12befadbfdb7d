"""Device seconds per training step in the model's forward and backward
passes (span ``model_fwd``; the backward and recomputed forward ops keep
the scope), mean over chips (``bench.phases``)."""
from bench import phases

UNIT = "s"


def read(rec, trace):
    return phases.read_spans(rec, trace, ("model_fwd",))
