"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
readers need.

Devices are the planes named ``/device:TPU:<n>``; on each, the ``XLA
Ops`` line holds one event per operation run, named by its HLO line
(instruction name and opcode are read from it), and the ``XLA Modules``
line one event per program run; ``Async XLA Ops`` holds the spans of
asynchronous ops (a collective-permute from its start to its done).
Pallas kernels appear as custom calls named after the kernel's jitted
wrapper. The host's ``/host:CPU`` plane holds what the host threads
were doing, the benchmark's own ``bench.*`` annotations among them; an
idle gap of the device is put down to the innermost host event in it.

The result is small and JSON-able (``tests/bench/data`` keeps one):

* ``window_s``, ``busy_s``: the traced window's length and the device's
  busy time (union of its operations' intervals), averaged over chips;
* ``devices``: per chip ``busy_s``; ``ops`` {HLO instruction:
  [count, seconds]}; ``categories`` {HLO opcode: seconds}; ``modules``
  {program name: [count, seconds]};
  ``permute_s`` and ``permute_exposed_s``, the time in collective-
  permutes and the part of it during which no other operation ran;
* ``device_ops``: the ten operations that took most time (chip 0);
* ``idle_gaps``: the ten longest idle gaps of chip 0, each with the host
  event it fell in.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
PERMUTE = "collective-permute"
DEVICE_LINES = ("XLA Ops", "Async XLA Ops", "XLA Modules")


def hlo_op(text: str):
    """(instruction, opcode) of an op event, whose name is the HLO line
    ``%sort.1 = (f32[..], s32[..]) sort(...)``; Pallas kernels carry
    their jitted wrapper's name, e.g. ``paged_flash_decode_pallas.2``."""
    if not text.startswith("%"):
        return text, None
    name, _, rest = text[1:].partition(" = ")
    m = OPCODE.search(" " + rest)
    return name, m.group(1) if m else None


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce_planes(planes, chips: int, window_s: float) -> dict:
    """``planes``: [(plane name, {line name: [(name, start_ns, end_ns,
    opcode)]})] as read from a trace (see ``read``)."""
    devices, host = [], []
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if m and int(m.group(1)) < chips:
            devices.append((int(m.group(1)), lines))
        elif pname.startswith("/host:"):
            for evs in lines.values():
                host.extend(evs)
    devices.sort()
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    out_devices = []
    for _, lines in devices:
        ops = lines.get("XLA Ops", [])
        merged = union([(s, e) for _, s, e, _ in ops])
        per_op, per_cat = {}, {}
        for name, s, e, cat in ops:
            c = per_op.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
            per_cat[cat or "unknown"] = per_cat.get(cat or "unknown", 0.0) \
                + (e - s) * 1e-9
        # a permute is in flight from its start op to its done op: the
        # async line holds that span, the ops line the waits
        permutes = union([(s, e) for n, s, e, _ in
                          ops + lines.get("Async XLA Ops", [])
                          if PERMUTE in n])
        others = union([(s, e) for n, s, e, _ in ops if PERMUTE not in n])
        modules = {}
        for name, s, e, _ in lines.get("XLA Modules", []):
            c = modules.setdefault(name.split("(")[0], [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
        out_devices.append({
            "busy_s": length(merged) * 1e-9, "ops": per_op,
            "categories": per_cat, "modules": modules,
            "permute_s": length(permutes) * 1e-9,
            "permute_exposed_s": (length(permutes)
                                  - overlap(permutes, others)) * 1e-9,
            "_merged": merged})
    first = out_devices[0]
    top = sorted(first["ops"].items(), key=lambda kv: -kv[1][1])[:10]
    gaps = [(s1, e0) for (_, s1), (e0, _) in zip(first["_merged"][:-1],
                                                 first["_merged"][1:])]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    for d in out_devices:
        del d["_merged"]
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in out_devices) / len(out_devices),
        "devices": out_devices,
        "device_ops": [[name, secs] for name, (_, secs) in top],
        "idle_gaps": [[_host_label(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def _host_label(host, t) -> str:
    inside = [(e - s, name) for name, s, e, _ in host if s <= t < e]
    return min(inside)[1] if inside else "none"


def read(path) -> list:
    """The trace's planes as plain lists (see ``reduce_planes``)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = DEVICE_PLANE.match(plane.name)
        lines = {}
        for line in plane.lines:
            if device and line.name not in DEVICE_LINES:
                continue
            evs = []
            for e in line.events:
                name, op = hlo_op(e.name) if device else (e.name, None)
                evs.append((name, e.start_ns, e.start_ns + e.duration_ns, op))
            lines[line.name] = evs
        planes.append((plane.name, lines))
    return planes


def reduce(path, chips: int, window_s: float) -> dict:
    return reduce_planes(read(path), chips, window_s)
