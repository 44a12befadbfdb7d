"""Device seconds per training step in the fixed-k index draws (span
``sdm_draw``: the sender's own draw and the receivers' regeneration of
their senders' draws, uniforms and top-k), mean over chips
(``bench.phases``)."""
from bench import phases

UNIT = "s"


def read(rec, trace):
    return phases.read_spans(rec, trace, ("sdm_draw",))
