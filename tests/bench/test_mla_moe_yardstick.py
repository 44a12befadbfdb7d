"""The expert model's arithmetic and readers: FLOPs and parameters of the
Moonlight cut by hand, the span an op's device time goes to, and the
four per-layer readers, silent on a dense cell's record."""
import re

import jax
import pytest

from bench import harness, mla_moe_flops, moe_spans, phases

MOON = "moonlight-16b-a3b-5layer"
NEW = ("mla_attn_s.train", "moe_route_s.train", "moe_experts_s.train",
       "moe_gmm_roofline.train")


def test_moonlight_counts_by_hand():
    m = harness.load_config(MOON)["model"]
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    dense = 3 * 2048 * 11264
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + 6 * 8 / 64 * expert + 2 * expert
    head = 2048 * 20480
    assert mla_moe_flops.matmul_params(m) == 5 * mla + dense + 4 * moe + head
    attn = 2 * 16 * (128 + 64 + 128) * (8192 + 1) / 2 * 5
    assert mla_moe_flops.train_flops_per_token(m, 8192) == pytest.approx(
        3 * (2 * (5 * mla + dense + 4 * moe + head) + attn))
    assert mla_moe_flops.train_flops_per_token(m, 8192) == pytest.approx(
        2.283088896e9)
    layer = mla + 512 + 2048
    want = (layer + dense + 2048) + 4 * (
        layer + 2048 * 64 + 8 * expert + 2 * expert + 2048) \
        + 2 * head + 2048
    assert mla_moe_flops.param_count(m) == want == 568_484_352
    assert mla_moe_flops.gmm_flops_per_row(m) == 18 * 2048 * 1408


def test_reference_and_system_hold_the_counted_parameters():
    from bench.configs import mla_moe_reference as ref
    from repro.models import transformer
    from repro.models.config import ModelConfig

    m = harness.load_config(MOON)["model"]
    sizes = lambda t: sum(int(jax.numpy.prod(jax.numpy.array(s)))
                          for s in jax.tree.leaves(
                              t, is_leaf=lambda v: isinstance(v, tuple)
                              and all(isinstance(e, int) for e in v)))
    specs = jax.tree.map(lambda s: s[0], ref.param_specs(m),
                         is_leaf=ref._is_spec)
    assert sizes(specs) == mla_moe_flops.param_count(m)
    assert sizes(transformer.param_shapes(ModelConfig(**m))) == \
        mla_moe_flops.param_count(m)


@pytest.mark.parametrize("name,op_name,want", [
    ("fusion.1", "jit(train_step)/jvp(model_fwd)/while/body/closed_call/"
     "moe_experts/dot_general", "moe_experts"),
    ("ragged-dot-none.1", "ragged-dot-none", "moe_experts"),
    ("sort.3", "jit(train_step)/jvp(model_fwd)/while/body/moe_route/sort",
     "moe_route"),
    ("fusion.7", "jit(train_step)/transpose(jvp(model_fwd))/jvp(model_fwd)/"
     "checkpoint/rematted_computation/mla_attn/dot_general", "mla_attn"),
    ("fusion.9", "jit(train_step)/jvp(model_fwd)/dot_general", "model_fwd"),
    ("fusion.2", "jit(train_step)/sdm_mask/mul", "sdm_mask"),
])
def test_span_of_an_op(name, op_name, want):
    assert moe_spans.span_of(phases.phase(op_name), name, op_name) == want


def test_step_ops_carry_the_new_spans():
    """The tiny expert model's compiled step: ops under each new span, in
    the forward and in the backward pass, as the reader sees them."""
    import sys

    sys.path.insert(0, str(harness.ROOT / "tests" / "bench"))
    from bench.drivers.train_moe import Program
    from fault_run import tiny_cell
    from moe_run import CONFIG

    w = tiny_cell(harness.load_workload("moonlight-sdm-1node-randk-8k"))
    prog = Program(w, CONFIG, seed=7, devices=jax.devices()[:1])
    ops = re.findall(r'^\s*%?(\S+) = .*op_name="([^"]*)"',
                     prog.compiled.as_text(), re.M)
    spans = {moe_spans.span_of(phases.phase(o), n, o) for n, o in ops}
    back = {moe_spans.span_of(phases.phase(o), n, o) for n, o in ops
            if "transpose(" in o}
    assert set(moe_spans.SPANS) <= spans, spans
    assert set(moe_spans.SPANS) <= back, back


def test_new_readers_are_silent_on_a_dense_cell(monkeypatch):
    monkeypatch.setattr(phases, "newest_trace",
                        lambda: pytest.fail("a dense cell reads no trace"))
    readers = harness.metric_readers()
    rec = {"kind": "train", "device_kind": "TPU v5 lite",
           "counters": {"steps": 4, "chips": 1, "flops_per_token": 1.0}}
    for name in NEW:
        assert readers[name].read(rec, {"devices": [{}]}) is None, name


def test_new_readers_by_hand(monkeypatch, tmp_path):
    path = tmp_path / "w.xplane.pb"
    monkeypatch.setattr(phases, "newest_trace", lambda: path)
    monkeypatch.setattr(moe_spans, "_CACHE", {str(path): [
        {"mla_attn": 0.8, "moe_route": 0.2, "moe_experts": 0.4,
         "model_fwd": 1.0}]})
    readers = harness.metric_readers()
    rec = {"kind": "train", "device_kind": "TPU v5 lite",
           "counters": {"steps": 4, "chips": 1, "moe_rows": 1000,
                        "gmm_flops_per_row": 1e9}}
    read = lambda n: readers[n].read(rec, {"devices": [{}]})
    assert read("mla_attn_s.train") == pytest.approx(0.2)
    assert read("moe_route_s.train") == pytest.approx(0.05)
    assert read("moe_experts_s.train") == pytest.approx(0.1)
    assert read("moe_gmm_roofline.train") == pytest.approx(
        100 * 1000 * 1e9 / 0.4 / 197e12)
