"""The benchmark's own arithmetic: FLOPs and bytes from shapes, the
table of peaks, and how cells and metrics are found by name."""
import json
import uuid

import pytest

from bench import flops, harness, peaks


def model(name):
    return harness.load_config(name)["model"]


def test_phi3_counts_by_hand():
    m = model("phi3-medium-14b-1layer")
    layer = 5120 * (40 + 2 * 10) * 128 + 40 * 128 * 5120 + 3 * 5120 * 17920
    assert flops.matmul_params(m) == layer + 5120 * 8016
    attn = 4 * 40 * 128 * (2048 + 1) / 2
    assert flops.train_flops_per_token(m, 2048) == 3 * (
        2 * (layer + 5120 * 8016) + attn)
    assert flops.param_count(m) == layer + 2 * 5120 + 2 * 8192 * 5120 + 5120
    pack = flops.fixedk_pack(m, {"block": 128, "p": 0.2})
    assert pack == {"kept_blocks": 663_576, "block": 128,
                    "bytes": 663_576 * 128 * 4 * 2 + 663_576 * 4}


def test_chatglm3_counts_by_hand():
    m = model("chatglm3-6b-2layer")
    layer = 4096 * (32 + 2 * 2) * 128 + 32 * 128 * 4096 + 3 * 4096 * 13696
    assert flops.matmul_params(m) == 2 * layer + 4096 * 8128
    assert flops.param_count(m) == 2 * (layer + 2 * 4096 + 36 * 128) \
        + 2 * 8192 * 4096 + 4096 == 475_034_624
    assert flops.fixedk_pack(m, {"block": 1, "p": 0.2}) is None
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(2.74784256e9)


def test_peaks_of_v5e_and_unknown_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_a_new_cell_and_metric_are_found_by_name():
    tag = f"t{uuid.uuid4().hex[:8]}"
    cell = harness.BENCH / "workloads" / f"{tag}.json"
    metric = harness.BENCH / "metrics" / f"{tag}.train.py"
    cell.write_text(json.dumps({"config": "phi3-medium-14b-1layer",
                                "traffic": "sdm_ring4_block128", "chips": 1}))
    metric.write_text("UNIT = 's'\n\ndef read(rec, trace):\n"
                      "    return rec['counters'].get('x')\n")
    try:
        w = harness.load_workload(tag)
        assert w["name"] == tag
        assert harness.load_config(w["config"])["model"]["d_model"] == 5120
        assert harness.driver(w["kind"]).run is not None
        reader = harness.metric_readers()[f"{tag}.train"]
        assert reader.read({"counters": {"x": 3.0}}, {}) == 3.0
        assert reader.read({"counters": {}}, {}) is None
    finally:
        cell.unlink()
        metric.unlink()


def test_every_cell_names_files_that_exist():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    readers = harness.metric_readers()
    for w in bench["workloads"]:
        cell = harness.load_workload(w["name"])
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        harness.load_config(cell["config"])
    for m in bench["per_layer"]:
        assert readers[m["name"]].UNIT == m["unit"]
