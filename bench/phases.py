"""Device time per phase of the training step, read from a profiler trace.

The system names each phase of its distributed step with
``jax.named_scope`` (``SPANS``). XLA keeps the scope path in each HLO
instruction's ``op_name`` metadata, and the profiler keeps the optimized
HLO of every program it saw in the trace's ``/host:metadata`` plane (one
``Hlo Proto`` per program, under the program's name, e.g.
``jit_step(12)``). So each op of a device's ``XLA Ops`` line is put down
to the innermost span named in its instruction's ``op_name``, found in
the program that the ``XLA Modules`` event around it names; an op in no
span is ``unscoped``. A fusion whose own ``op_name`` names no span takes
the first span among the ops fused into it, root first; a Pallas
kernel's event, named after its jitted wrapper, takes the span that
wrapper ran under.

Each instant of a device's busy time goes to the innermost op running
then (the one that started last: a ``while`` gives way to the ops of its
body), so the phases of a device add up to its busy time, and no
instant counts twice.

The reduction reads the window's own trace (the newest under
``harness.TRACE_DIR``) once per process, after the window has closed;
an untraced run never comes here. A program without the spans (an
older build) puts every op under ``unscoped``, and the readers of the
``*_s.train`` phase metrics then report nothing.

  python3 bench/phases.py <trace.xplane.pb> [chips]   # prints the split
"""
from __future__ import annotations

import bisect
import heapq
import json
import pathlib
import re
import sys
import time

SPANS = ("model_fwd", "sdm_draw", "sdm_pack", "sdm_permute", "sdm_mask",
         "sdm_mix")
UNSCOPED = "unscoped"
_SPAN = re.compile(r"\b(" + "|".join(SPANS) + r")\b")
_HLO_PROTO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"


def phase(op_name: str) -> str:
    """The innermost span named in an ``op_name`` path. A backward op
    (``transpose(jvp(model_fwd))``) and a recomputed forward op keep
    their forward span."""
    found = _SPAN.findall(op_name)
    return found[-1] if found else UNSCOPED


# -- protobuf wire format: just the fields read here ------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _first(buf, num: int, default=None):
    for k, v in _fields(buf):
        if k == num:
            return v
    return default


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def hlo_protos(xspace: bytes) -> dict:
    """{program name: serialized ``HloProto``} from the ``/host:metadata``
    plane of a serialized ``XSpace`` (an ``.xplane.pb`` file)."""
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1 or _text(_first(plane, 2, b"")) != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for k, v in _fields(plane):
            if k == 4:                    # event_metadata map entry
                events.append(_first(v, 2, b""))
            elif k == 5:                  # stat_metadata map entry
                md = _first(v, 2, b"")
                stat_names[_first(md, 1, 0)] = _text(_first(md, 2, b""))
        for md in events:
            name = ""
            for k, v in _fields(md):
                if k == 2:
                    name = _text(v)
                elif k == 5:              # XStat
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == _HLO_PROTO_STAT \
                            and 6 in stat:
                        out[name] = bytes(stat[6])
    return out


def _ints(v) -> list:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def op_names(module: bytes) -> dict:
    """{instruction name: op_name} over every computation of a serialized
    ``HloModuleProto``. An instruction that calls computations and whose
    own ``op_name`` names no span (a fusion that XLA rooted at a tuple, a
    reshape or an in-place update; a loop it built) takes the first
    ``op_name`` that does among the instructions it calls, the root
    first."""
    comps = {}                            # computation id: instructions
    for k, comp in _fields(memoryview(module)):
        if k != 3:                        # computations
            continue
        cid, instrs = None, []
        for j, v in _fields(comp):
            if j == 5:
                cid = v
            elif j == 2:                  # instructions
                name, op_name, calls = "", "", []
                for f, x in _fields(v):
                    if f == 1:
                        name = _text(x)
                    elif f == 7:          # OpMetadata
                        op_name = _text(_first(x, 2, b""))
                    elif f == 38:         # called_computation_ids
                        calls += _ints(x)
                instrs.append((name, op_name, calls))
        comps[cid] = instrs
    out, best = {}, {}

    def callee_op_name(cid):
        if cid not in best:
            best[cid] = ""                # guards against a cycle
            found = [resolve(o, cs) for _, o, cs in reversed(comps.get(cid, []))]
            best[cid] = next((o for o in found if _SPAN.search(o)),
                             next((o for o in found if o), ""))
        return best[cid]

    def resolve(op_name, calls):
        if _SPAN.search(op_name) or not calls:
            return op_name
        found = [callee_op_name(c) for c in calls]
        return next((o for o in found if _SPAN.search(o)),
                    op_name or next((o for o in found if o), ""))

    for instrs in comps.values():
        for name, op_name, calls in instrs:
            out[name] = resolve(op_name, calls)
    return out


def programs(xspace: bytes) -> dict:
    """{program name: {instruction: op_name}} of every program in a
    trace's metadata (an ``HloProto`` holds its module as field 1)."""
    return {name: op_names(_first(memoryview(proto), 1, b""))
            for name, proto in hlo_protos(xspace).items()}


# -- device time per phase ----------------------------------------------------

def exclusive(ops) -> dict:
    """{label: seconds} from [(start_ns, end_ns, label)]: each instant in
    any interval goes to the interval that started last among those
    running then. The values add up to the length of the union."""
    out = {}
    ops = sorted(ops)
    running = []                          # (-start, end, label)
    i, n, t = 0, len(ops), None
    while i < n or running:
        if not running:
            t = ops[i][0] if t is None else max(t, ops[i][0])
        while i < n and ops[i][0] <= t:
            s, e, label = ops[i]
            heapq.heappush(running, (-s, e, label))
            i += 1
        while running and running[0][1] <= t:
            heapq.heappop(running)
        if not running:
            continue
        _, end, label = running[0]
        stop = min(end, ops[i][0]) if i < n else end
        out[label] = out.get(label, 0.0) + (stop - t) * 1e-9
        t = stop
    return out


_WRAPPER = re.compile(r"jit\(([^()]+)\)")
_SUFFIX = re.compile(r"\.\d+$")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def _program(op_names_of: dict, name) -> dict:
    """The instructions of the program a module event names: by its name,
    or else by the one program of that name less its ``(id)``."""
    if name in op_names_of:
        return op_names_of[name]
    base = _PROGRAM_ID.sub("", name or "")
    same = [v for k, v in op_names_of.items() if _PROGRAM_ID.sub("", k) == base]
    return same[0] if len(same) == 1 else {}


def _by_wrapper(names: dict) -> dict:
    """{jitted wrapper: an op_name traced under it}. A Pallas kernel's
    event carries its jitted wrapper's name (``fixedk_gather_pack_pallas.1``),
    not its custom-call's."""
    out = {}
    for op_name in names.values():
        for wrapper in _WRAPPER.findall(op_name):
            out.setdefault(wrapper, op_name)
    return out


def _op_times(lines, op_names_of: dict) -> dict:
    """{(phase, instruction, op_name): seconds} of one device's ops."""
    mods = sorted((s, e, name) for name, s, e, _ in
                  lines.get("XLA Modules", []))
    starts = [s for s, _, _ in mods]
    resolved, spans = {}, []
    for name, s, e, _ in lines.get("XLA Ops", []):
        j = bisect.bisect_right(starts, s) - 1
        prog = mods[j][2] if j >= 0 and s < mods[j][1] else None
        if prog not in resolved:
            names = _program(op_names_of, prog)
            resolved[prog] = names, _by_wrapper(names)
        names, wrappers = resolved[prog]
        op_name = names.get(name)
        if op_name is None:
            op_name = wrappers.get(_SUFFIX.sub("", name), "")
        spans.append((s, e, (phase(op_name), name, op_name)))
    return exclusive(spans)


def reduce_planes(planes, chips: int, op_names_of: dict) -> dict:
    """``planes`` as ``bench.trace.read`` gives them; ``op_names_of``
    {program name: {instruction: op_name}}. Per device: ``phase_s``
    {phase: seconds}, which adds up to its busy time; ``top``: per phase
    the three longest ops of chip 0 [instruction, seconds, op_name]."""
    from bench.trace import DEVICE_PLANE

    devices = []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if m and int(m.group(1)) < chips:
            devices.append((int(m.group(1)), lines))
    devices.sort(key=lambda d: d[0])
    out, top = [], {}
    for i, (_, lines) in enumerate(devices):
        phase_s = {}
        for (ph, name, op_name), secs in _op_times(lines,
                                                   op_names_of).items():
            phase_s[ph] = phase_s.get(ph, 0.0) + secs
            if i == 0:
                top.setdefault(ph, []).append([name, secs, op_name])
        out.append({"phase_s": phase_s})
    top = {ph: sorted(v, key=lambda r: -r[1])[:3] for ph, v in top.items()}
    return {"devices": out, "top": top}


def reduce(path, chips: int) -> dict:
    from bench import trace

    raw = pathlib.Path(path).read_bytes()
    return reduce_planes(trace.read(path), chips, programs(raw))


# -- what the metric readers share ---------------------------------------------

_CACHE = {}          # trace path: its split, shared by the phase metrics


def newest_trace():
    from bench.harness import TRACE_DIR

    found = sorted(TRACE_DIR.glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def window_phases(rec, trace_summary):
    """The phase split of the run's traced window, or None where there is
    no trace or it is not the one ``trace_summary`` was reduced from.
    Prints, once, a ``phases`` line on standard error: per phase the
    device seconds per step (mean over chips) and the three longest ops
    of chip 0 with their ``op_name`` paths; ``reduce_s``, the seconds
    this reduction took."""
    path = newest_trace()
    if path is None:
        return None
    key = str(path)
    if key not in _CACHE:
        t0 = time.perf_counter()
        chips = len(trace_summary["devices"])
        split = reduce(path, chips)
        busy = [sum(d["phase_s"].values()) for d in split["devices"]]
        want = [d["busy_s"] for d in trace_summary["devices"]]
        if len(busy) != len(want) or any(
                abs(a - b) > 1e-6 * max(b, 1e-9) for a, b in zip(busy, want)):
            split = None
        _CACHE[key] = split
        if split is not None:
            steps = rec["counters"]["steps"]
            line = {ph: {"s_per_step": per_step(split, (ph,), steps),
                         "top": split["top"].get(ph, [])}
                    for ph in SPANS + (UNSCOPED,)}
            line["reduce_s"] = time.perf_counter() - t0
            print(f"phases {json.dumps(line)}", file=sys.stderr, flush=True)
    return _CACHE[key]


def per_step(split, spans, steps: int):
    """Device seconds per step in ``spans``, mean over chips; None where
    the window ran none of them."""
    secs = [sum(d["phase_s"].get(s, 0.0) for s in spans)
            for d in split["devices"]]
    if not any(secs):
        return None
    return sum(secs) / len(secs) / steps


def read_spans(rec, trace_summary, spans):
    """A phase metric's reading: device seconds per step in ``spans``."""
    if rec["kind"] != "train":
        return None
    split = window_phases(rec, trace_summary)
    if split is None:
        return None
    return per_step(split, spans, rec["counters"]["steps"])


if __name__ == "__main__":
    sys.path[:0] = [str(pathlib.Path(__file__).resolve().parents[1])]
    xplane = sys.argv[1]
    print(json.dumps(reduce(xplane, int(sys.argv[2]) if len(sys.argv) > 2
                            else 1)))
