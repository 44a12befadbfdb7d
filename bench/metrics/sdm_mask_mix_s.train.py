"""Device seconds per training step in the coordinate clip and Gaussian
mask (span ``sdm_mask``) and the theta-mixing and differential (span
``sdm_mix``), mean over chips (``bench.phases``). XLA fuses the two into
shared fusions, which carry only their root's scope, so they are read
as one."""
from bench import phases

UNIT = "s"


def read(rec, trace):
    return phases.read_spans(rec, trace, ("sdm_mask", "sdm_mix"))
