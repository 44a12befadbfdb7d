"""Device seconds per training step converting between dense planes and
the wire payload (span ``sdm_pack``: gather, scale, scatter, weighted
neighbour sum) and in the collective-permutes (span ``sdm_permute``),
mean over chips (``bench.phases``)."""
from bench import phases

UNIT = "s"


def read(rec, trace):
    return phases.read_spans(rec, trace, ("sdm_pack", "sdm_permute"))
