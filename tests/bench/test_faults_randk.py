"""``chatglm3-sdm-1node-randk``: a sound tiny run is correct under the cell's limits, and
each fault the cell can have, planted in the timed path, makes
``correct`` come out false (see fault_run.py)."""
import pytest

from _faults import run

CELL = "chatglm3-sdm-1node-randk"


def test_sound_run_is_correct():
    out = run(CELL, "none")
    assert out["correct"] is True, out["check"]


@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_fault_is_caught(fault):
    out = run(CELL, fault)
    assert out["correct"] is False, out["check"]


def test_zeroed_leaf_gradient_fails_the_projection():
    out = run(CELL, "zero_leaf")
    gap = out["check"]["grad_proj_gap"]
    assert out["correct"] is False and gap["value"] > gap["limit"], \
        out["check"]
