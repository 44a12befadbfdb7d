"""The trace reduction (``bench/trace.py``): by hand on a small made-up
trace, and on a trace recorded on the chip (``data/``)."""
import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def op(name, start_us, end_us, cat="convolution"):
    return [name, start_us * 1000, end_us * 1000, cat]


def test_hlo_op_names():
    assert trace.hlo_op("%sort.1 = (f32[8]{0:T(1024)}, s32[8]{0}) sort(f32[8] "
                        "%a, s32[8] %b)") == ("sort.1", "sort")
    assert trace.hlo_op("%fusion.3 = f32[4]{0:T(1024)} fusion(f32[4]{0} %x), "
                        "kind=kLoop") == ("fusion.3", "fusion")
    assert trace.hlo_op("jit_step(12)") == ("jit_step(12)", None)


def test_reduction_by_hand():
    dev = {"XLA Ops": [op("fusion.1", 0, 10), op("fusion.2", 5, 20),
                       op("collective-permute-start.3", 20, 40, "permute"),
                       op("fusion.4", 30, 35),
                       op("fixedk_gather_pack_pallas", 50, 60,
                          "custom-call")],
           "XLA Modules": [["jit_step(7)", 0, 60_000, None]]}
    host = {"python": [["bench.step", 0, 100_000, None],
                       ["PjitFunction(step)", 1000, 48_000, None]]}
    s = trace.reduce_planes([("/device:TPU:0", dev), ("/host:CPU", host)],
                            chips=1, window_s=100e-6)
    d = s["devices"][0]
    assert d["busy_s"] == pytest.approx(50e-6)       # [0,40) and [50,60)
    assert s["busy_s"] == pytest.approx(50e-6)
    assert d["permute_s"] == pytest.approx(20e-6)
    assert d["permute_exposed_s"] == pytest.approx(15e-6)   # less [30,35)
    assert d["ops"]["fusion.2"] == [1, pytest.approx(15e-6)]
    assert d["ops"]["fixedk_gather_pack_pallas"] == [1, pytest.approx(10e-6)]
    assert d["categories"]["custom-call"] == pytest.approx(10e-6)
    assert d["modules"] == {"jit_step": [1, pytest.approx(60e-6)]}
    assert s["device_ops"][0][0] in ("fusion.2", "collective-permute-start.3")
    # one idle gap, [40, 50) us, inside the innermost host event there
    assert s["idle_gaps"] == [["PjitFunction(step)", pytest.approx(10e-6)]]


def test_planes_of_other_chips_are_left_out():
    dev = {"XLA Ops": [op("fusion.1", 0, 10)]}
    s = trace.reduce_planes([("/device:TPU:0", dev), ("/device:TPU:1", dev),
                             ("/device:TPU:2", {"XLA Ops": []})],
                            chips=2, window_s=20e-6)
    assert len(s["devices"]) == 2
    assert s["busy_s"] == pytest.approx(10e-6)


@pytest.mark.parametrize("path", sorted(DATA.glob("*.trace.json")),
                         ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    rec = json.loads(path.read_text())
    s = trace.reduce_planes(rec["planes"], rec["chips"], rec["window_s"])
    planes = dict((n, lines) for n, lines in rec["planes"])
    for i, d in enumerate(s["devices"]):
        ops = planes[f"/device:TPU:{i}"]["XLA Ops"]
        # busy time: the union of op intervals, checked by a sweep over
        # every op boundary
        cuts = sorted({t for _, a, b, _ in ops for t in (a, b)})
        busy = sum(b - a for a, b in zip(cuts[:-1], cuts[1:])
                   if any(s0 <= a and b <= e0 for _, s0, e0, _ in ops))
        assert d["busy_s"] == pytest.approx(busy * 1e-9)
        assert 0 < d["busy_s"] <= rec["window_s"]
        assert sum(n for n, _ in d["ops"].values()) == len(ops)
        assert sum(d["categories"].values()) == pytest.approx(
            sum(e - a for _, a, e, _ in ops) * 1e-9)
        for name, want in rec.get("kernels", {}).items():
            got = sum(s_ for n, (_, s_) in d["ops"].items() if name in n)
            assert got == pytest.approx(want[i])
    assert s["device_ops"] and len(s["idle_gaps"]) <= 10
