"""Device time in the spans of the latent-attention and expert layers, read
from a traced window as ``bench.phases`` reads the step's phases.

The system names its latent-attention sublayer ``mla_attn``, its
routing (router, top-k, weights, the sort into expert order, the gather
of the rows and the weighted combine back) ``moe_route`` and its experts'
grouped matmuls ``moe_experts``, all inside ``model_fwd``. Each op's
exclusive device time (``bench.phases``) goes to the innermost of these
spans named in its ``op_name``, else to the phase ``bench.phases`` gave
it. XLA emits ``jax.lax.ragged_dot`` as kernels of its own named
``ragged-dot-*`` and gives them that name as ``op_name`` too, dropping
the span, so they go to ``moe_experts`` by name.

Only a training window of a model with expert layers is read (its record
counts ``moe_rows``); elsewhere, and where the trace is not the window's,
the readers report nothing.
"""
from __future__ import annotations

import pathlib
import re

from bench import phases

SPANS = ("mla_attn", "moe_route", "moe_experts")
_SPAN = re.compile(r"\b(" + "|".join(SPANS) + r")\b")
GMM_KERNEL = "ragged-dot"

_CACHE = {}          # trace path: {span: device seconds}, per chip


def span_of(phase: str, name: str, op_name: str) -> str:
    """The span an op's time goes to (see the module doc)."""
    if name.startswith(GMM_KERNEL):
        return "moe_experts"
    found = _SPAN.findall(op_name)
    return found[-1] if found else phase


def split(path, chips: int) -> list:
    """Per chip, {span or phase: device seconds} over the whole trace."""
    from bench import trace

    raw = pathlib.Path(path).read_bytes()
    op_names_of = phases.programs(raw)
    out = []
    for pname, lines in trace.read(path):
        m = trace.DEVICE_PLANE.match(pname)
        if not m or int(m.group(1)) >= chips:
            continue
        secs = {}
        for key, s in phases._op_times(lines, op_names_of).items():
            span = span_of(*key)
            secs[span] = secs.get(span, 0.0) + s
        out.append(secs)
    return out


def read_span(rec, trace_summary, span: str):
    """Device seconds per step in ``span``, mean over chips; None where
    the window ran none of it or is not a traced window of an expert
    model."""
    if rec["kind"] != "train" or "moe_rows" not in rec["counters"]:
        return None
    path = phases.newest_trace()
    if path is None:
        return None
    key = str(path)
    if key not in _CACHE:
        _CACHE[key] = split(path, len(trace_summary["devices"]))
    secs = [d.get(span, 0.0) for d in _CACHE[key]]
    if not any(secs):
        return None
    return sum(secs) / len(secs) / rec["counters"]["steps"]
