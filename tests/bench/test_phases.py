"""Device time per phase (``bench/phases.py``): the ``op_name`` rule, the
reduction by hand and on the recorded chip traces, the protobuf reading
of a trace's programs, and the spans of both cells' steps, built at a
tiny size on the CPU (``phase_run.py``)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import harness, phases, trace

HERE = pathlib.Path(__file__).resolve().parent
DATA = HERE / "data"


def op(name, start_us, end_us, cat="fusion"):
    return [name, start_us * 1000, end_us * 1000, cat]


@pytest.mark.parametrize("op_name,want", [
    ("jit(train_step)/shard_map/jvp(model_fwd)/dot_general", "model_fwd"),
    ("jit(train_step)/shard_map/transpose(jvp(model_fwd))/dot_general",
     "model_fwd"),
    ("jit(train_step)/shard_map/sdm_pack/sdm_pack/sdm_draw/top_k",
     "sdm_draw"),
    ("jit(train_step)/shard_map/sdm_pack/sdm_permute/ppermute",
     "sdm_permute"),
    ("jit(train_step)/shard_map/sdm_mask/jit(_normal)/while", "sdm_mask"),
    ("jit(train_step)/shard_map/psum", "unscoped"),
    ("jit(f)/my_model_fwd2/add", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_span(op_name, want):
    assert phases.phase(op_name) == want


def test_exclusive_time_by_hand():
    # a while [0, 100) enclosing its body's ops [10, 40) and [50, 60),
    # then an op that overlaps the one before it
    got = phases.exclusive([(0, 100, "w"), (10, 40, "a"), (50, 60, "b"),
                            (120, 150, "c"), (140, 160, "d")])
    assert got == pytest.approx({"w": 60e-9, "a": 30e-9, "b": 10e-9,
                                 "c": 20e-9, "d": 20e-9})
    assert phases.exclusive([]) == {}


def test_reduction_by_hand():
    dev = {"XLA Modules": [["jit_step(3)", 0, 200_000, None],
                           ["jit_other(4)", 300_000, 400_000, None]],
           "XLA Ops": [op("while.1", 0, 100, "while"),
                       op("fusion.2", 10, 40),
                       op("sort.3", 50, 60, "sort"),
                       op("copy.4", 120, 150, "copy"),
                       op("fixedk_gather_pack_pallas.1", 150, 170,
                          "custom-call"),
                       op("fusion.2", 300, 310)]}
    names = {"jit_step(3)": {
        "while.1": "jit(step)/shard_map/sdm_mask/while",
        "fusion.2": "jit(step)/shard_map/sdm_mask/jit(_normal)/add",
        "sort.3": "jit(step)/shard_map/sdm_pack/sdm_draw/top_k",
        "custom-call.8": "jit(step)/shard_map/sdm_pack/"
                         "jit(fixedk_gather_pack_pallas)/dynamic_slice",
        "copy.4": ""}}
    planes = [("/device:TPU:0", dev), ("/host:CPU", {})]
    split = phases.reduce_planes(planes, 1, names)
    # a module event whose program id the metadata does not share
    renamed = {"jit_step(99)": names["jit_step(3)"]}
    assert phases.reduce_planes(planes, 1, renamed) == split
    got = split["devices"][0]["phase_s"]
    # the while counts once, beside its body's ops; the copy has no
    # op_name; the kernel's event is named after its jitted wrapper; the
    # other program's fusion.2 is not this step's
    assert got == pytest.approx({"sdm_mask": 90e-6, "sdm_draw": 10e-6,
                                 "sdm_pack": 20e-6, "unscoped": 40e-6})
    busy = trace.reduce_planes(planes, 1, 1e-3)["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)
    assert split["top"]["sdm_draw"] == [
        ["sort.3", pytest.approx(10e-6), names["jit_step(3)"]["sort.3"]]]


@pytest.mark.parametrize("path", sorted(DATA.glob("*.trace.json")),
                         ids=lambda p: p.stem)
def test_recorded_chip_trace_splits_its_busy_time(path):
    """Without the programs' HLO every op is unscoped, and each chip's
    split adds up to the busy time ``bench.trace`` reads."""
    rec = json.loads(path.read_text())
    busy = trace.reduce_planes(rec["planes"], rec["chips"], rec["window_s"])
    split = phases.reduce_planes(rec["planes"], rec["chips"], {})
    assert len(split["devices"]) == len(busy["devices"])
    for d, want in zip(split["devices"], busy["devices"]):
        assert set(d["phase_s"]) == {"unscoped"}
        assert d["phase_s"]["unscoped"] == pytest.approx(want["busy_s"])
    assert phases.per_step(split, ("model_fwd",), 1) is None


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields) -> bytes:
    """A protobuf message of (number, int | bytes | str) fields."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_programs_read_from_the_metadata_plane():
    ins = lambda name, op_name: _msg(
        (1, name), (2, "fusion"), (7, _msg((1, "add"), (2, op_name))),
        (35, 7))
    # fusion.3 has no op_name and fusion.4's names no span: each takes
    # the first one with a span among its fused instructions, root first
    # (called computation ids packed, then as one varint)
    fused = _msg((1, "fused"), (2, ins("add.1", "jit(step)/sdm_mix/add")),
                 (2, ins("broadcast.5", "jit(step)/broadcast_in_dim")),
                 (5, 11))
    module = _msg((1, "jit_step"), (3, fused), (3, _msg(
        (1, "main"), (2, ins("fusion.1", "jit(step)/sdm_mix/add")),
        (2, ins("copy.2", "")),
        (2, _msg((1, "fusion.3"), (38, _varint(11)))),
        (2, _msg((1, "fusion.4"), (7, _msg((2, "jit(step)/broadcast_in_dim"))),
                 (38, 11))),
        (5, 12))))
    hlo_proto = _msg((1, module))
    stat = _msg((1, 9), (6, hlo_proto))
    plane = _msg((1, 4), (2, "/host:metadata"),
                 (4, _msg((1, 1), (2, _msg((1, 1), (2, "jit_step(12)"),
                                           (5, stat))))),
                 (5, _msg((1, 9), (2, _msg((1, 9), (2, "Hlo Proto"))))))
    other = _msg((2, "/host:CPU"))
    xspace = _msg((1, other), (1, plane))
    assert phases.programs(xspace) == {"jit_step(12)": {
        "add.1": "jit(step)/sdm_mix/add",
        "broadcast.5": "jit(step)/broadcast_in_dim",
        "fusion.1": "jit(step)/sdm_mix/add", "copy.2": "",
        "fusion.3": "jit(step)/sdm_mix/add",
        "fusion.4": "jit(step)/sdm_mix/add"}}


def test_silent_without_a_trace(monkeypatch):
    monkeypatch.setattr(phases, "newest_trace", lambda: None)
    rec = {"kind": "train", "counters": {"steps": 1}}
    assert phases.read_spans(rec, {"devices": []}, ("sdm_draw",)) is None


def test_phase_metrics_read_one_reduction(monkeypatch, capsys, tmp_path):
    split = {"devices": [
        {"phase_s": {"model_fwd": 2.0, "sdm_draw": 1.0, "unscoped": 1.0}},
        {"phase_s": {"model_fwd": 4.0, "sdm_mix": 1.0, "unscoped": 1.0}}],
        "top": {"sdm_draw": [["sort.1", 1.0, "a/sdm_draw/top_k"]]}}
    reductions = []
    monkeypatch.setattr(phases, "_CACHE", {})
    monkeypatch.setattr(phases, "newest_trace", lambda: tmp_path / "x.pb")
    monkeypatch.setattr(phases, "reduce",
                        lambda path, chips: reductions.append(chips) or split)
    readers = harness.metric_readers()
    rec = {"kind": "train", "counters": {"steps": 2}}
    window = {"devices": [{"busy_s": 4.0}, {"busy_s": 6.0}]}
    read = lambda name: readers[name].read(rec, window)
    assert read("fwd_bwd_s.train") == pytest.approx((2 + 4) / 2 / 2)
    assert read("sdm_draw_s.train") == pytest.approx(1 / 2 / 2)
    assert read("sdm_mask_mix_s.train") == pytest.approx(1 / 2 / 2)
    assert read("sdm_exchange_s.train") is None
    assert reductions == [2]
    line, = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("phases ")]
    printed = json.loads(line[len("phases "):])
    assert printed["sdm_draw"]["top"] == split["top"]["sdm_draw"]
    assert printed["unscoped"]["s_per_step"] == pytest.approx(0.5)
    # a trace that is not the window's (its busy time differs): silent
    monkeypatch.setattr(phases, "_CACHE", {})
    window["devices"][0]["busy_s"] = 5.0
    assert read("fwd_bwd_s.train") is None


def _step_phases(cell: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(HERE / "phase_run.py"), cell],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,spans", [
    ("phi3-sdm-ring4-block128", phases.SPANS),
    ("chatglm3-sdm-1node-randk",
     tuple(s for s in phases.SPANS if s != "sdm_permute")),
], ids=["ring4", "randk"])
def test_step_carries_every_span(cell, spans):
    out = _step_phases(cell)
    assert any(n.startswith("jit_train_step") for n in out["programs"])
    for span in spans:
        assert out["phases"].get(span, 0) > 0, (span, out["phases"])
    if "sdm_permute" not in spans:      # one node: nothing on the wire
        assert "sdm_permute" not in out["phases"]
    # the backward pass keeps the forward's scope
    assert set(out["backward"]) == {"model_fwd"}, out["backward"]
