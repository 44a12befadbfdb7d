"""A node's own S(d) in the packed transports: a keep set drawn as a mask
(``sparsifier.fixedk_mask``) and applied as a dense select; the wire's
index lists: counted masks compacted to ascending order
(``sparsifier.mask_indices``) on the sender and on the receivers.

* On 4 CPU devices (subprocess, ``helpers/own_sdm_check.py``): the own
  S(d) of every packed transport equals the node's payload scattered back
  into a zero plane, and what a node receives equals its senders'
  payloads scattered back and weighed, bit for bit, payloads built from
  ``fixedk_indices`` — fixed-k at block 1 and 128, rows, per-node p, and
  the replica (union) transport — and the 4-node step draws its wire
  index lists by compaction, with no ``top_k`` and no sort.
* On one device: the step holds no sort, and no gather or scatter of
  the plane, and the launcher's banner says so.
* The sender's payload is its ascending keep list's rows times the
  scale, bit for bit, at every block width, dtype and budget.
"""
import contextlib
import io
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import gossip, sparsifier
from repro.launch import hlo_analysis

HELPER = pathlib.Path(__file__).parent / "helpers" / "own_sdm_check.py"
SRC = str(pathlib.Path(__file__).parent.parent / "src")

OWN_CASES = ["packed_b1", "packed_b1_bf16", "packed_b128", "rows",
             "hetp_rows", "hetp_b1", "hetp_b128", "union_b1",
             "union_hetp_b128", "union_rows"]


@pytest.fixture(scope="module")
def four_nodes() -> dict:
    out = subprocess.run(
        [sys.executable, str(HELPER)], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu"),
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = {}
    for line in out.stdout.splitlines():
        toks = line.split()
        if toks and toks[0] == "RANDOM":
            lines.setdefault("RANDOM", {})[toks[1]] = int(toks[2])
        elif toks and toks[0] in ("CASE", "DRAWS"):
            key = toks[1] if toks[0] == "CASE" else "DRAWS"
            rest = toks[2:] if toks[0] == "CASE" else toks[1:]
            lines[key] = {k: int(v) for k, v in zip(rest[::2], rest[1::2])}
    return lines


@pytest.mark.parametrize("case", OWN_CASES)
def test_own_sdm_equals_scattered_payload(four_nodes, case):
    got = four_nodes[case]
    assert got["EQUAL"] == 1, got
    assert got["NONZERO"] > 0, got


@pytest.mark.parametrize("case", OWN_CASES)
def test_received_equals_scattered_sender_payloads(four_nodes, case):
    """The neighbour sum (static ring) or per-slot increments (union
    transport) equal the senders' fixedk_indices payloads scattered back,
    bit for bit: sender and receivers agree on each list."""
    got = four_nodes[case]
    assert got["NB_EQUAL"] == 1, got
    assert got["NB_NONZERO"] > 0, got


@pytest.mark.parametrize("case", ["packed_b128", "union_b1"])
def test_sender_draws_its_round_key_once(four_nodes, case):
    """The own S(d)'s mask and the payload's index list read one draw of
    the node's round key: two draws in all, with the senders' batched
    one."""
    assert four_nodes["RANDOM"][case] == 2, four_nodes["RANDOM"]


def test_four_node_step_keeps_wire_topk_draws(four_nodes):
    """The 4-node ring step's wire lists are compacted masks: per plane
    one own mask, the payload's list and one list per sender (2 on the
    ring); no top_k draw and no sort is left."""
    draws = four_nodes["DRAWS"]
    assert draws["own_mask"] >= 1, draws
    assert draws["compact"] == 3 * draws["own_mask"], draws
    assert draws["SORT"] == 0, draws


def test_one_node_own_sdm_needs_no_wire_draw():
    """With no gossip round the transport draws no index list at all."""
    one = gossip.sequence_by_name("ring", 1)
    d = jnp.linspace(-1.0, 1.0, 1000)
    before = dict(gossip.draw_counts())
    own, nb_sum = gossip.exchange_packed(
        one, d, axis_name="data", base_key=jnp.zeros(2, jnp.uint32),
        step=jnp.int32(0), p=0.3, node_index=jnp.int32(0))
    after = gossip.draw_counts()
    assert after["compact"] == before["compact"]
    assert after["own_mask"] == before["own_mask"] + 1
    assert int((own != 0).sum()) == sparsifier.num_kept(1000, 0.3)
    assert not nb_sum.any()


# (block, dtype, p, plane rows of 128 coordinates): block widths on and
# off the lane, a bf16 plane and a per-node budget; then kept rows 4 and
# 16 of 64, scales 16 and 4
PACK_CASES = [
    pytest.param(128, jnp.float32, 0.2, 40, id="block128"),
    pytest.param(512, jnp.float32, 0.2, 40, id="block512"),
    pytest.param(1, jnp.float32, 0.2, 40, id="block1"),
    pytest.param(64, jnp.float32, 0.2, 40, id="block64"),
    pytest.param(128, jnp.bfloat16, 0.2, 40, id="bf16"),
    pytest.param(128, jnp.float32, (0.2, 0.5), 40, id="per_node_p"),
    pytest.param(128, jnp.float32, 0.0625, 64, id="kb4_scale16"),
    pytest.param(128, jnp.float32, 0.25, 64, id="kb16_scale4"),
]


@pytest.mark.parametrize("block,dtype,p,rows", PACK_CASES)
def test_sender_payload_is_its_keep_list_gathered(block, dtype, p, rows):
    """``gossip._packed_selection``'s payload equals a NumPy gather of
    the ascending keep list times the scale, bit for bit. With per-node
    p (node 0 keeps 0.2 of a payload padded to 0.5) the rows past the
    node's own budget are zero."""
    plane = jax.random.normal(jax.random.PRNGKey(block), (rows, 128))
    db = sparsifier.block_view(plane.astype(dtype).reshape(-1), block)
    nb = db.shape[0]
    kb, kb_me = gossip._kept(nb, p, 0)
    keys = sparsifier.score_keys(jax.random.PRNGKey(7), nb)
    keep = sparsifier.topk_mask(keys, kb_me, sparsifier.SCORE_BITS)
    got = gossip._packed_selection(db, keys, keep, kb, kb_me, p)
    assert got.shape == (kb, block) and got.dtype == dtype

    # lax.top_k's ranking: larger keys first, ties to the lower index
    order = np.argsort(-np.asarray(keys), kind="stable")
    sent = np.sort(order[:kb])
    rows_sent = np.asarray(db.astype(jnp.float32))[sent]
    if isinstance(p, tuple):
        own = np.isin(sent, order[:int(kb_me)])
        assert 0 < own.sum() < kb
        scale = np.float32(nb) / np.float32(int(kb_me))
        want = np.where(own[:, None], rows_sent * scale, np.float32(0))
        assert not np.asarray(got)[~own].any()
    else:
        # the scale as the payload's dtype holds it
        scale = np.float32(jnp.asarray(nb / kb, dtype))
        want = rows_sent * scale
    want = np.asarray(jnp.asarray(want).astype(dtype).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)


@pytest.fixture(scope="module")
def one_node_run():
    """The launcher's fixed-k run on a one-device mesh, and its stdout."""
    from repro.launch import train as train_mod

    args = train_mod.parse_args([
        "--arch", "chatglm3-6b", "--smoke", "--method", "sdm-dsgd",
        "--gossip-mode", "fixedk_packed", "--p", "0.2", "--sigma", "0.5",
        "--clip-c", "1.0", "--topology", "ring", "--mesh", "1",
        "--global-batch", "1", "--seq-len", "16", "--steps", "1"])
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        run = train_mod.train(args, configs.get_smoke_config("chatglm3-6b"))
    return run, buf.getvalue()


def test_one_node_step_has_no_sort_gather_or_scatter_of_plane(one_node_run):
    run, _ = one_node_run
    hlo = run.compiled.as_text()
    # XLA's CPU backend lowers top_k to a TopK custom call, the TPU's to a
    # sort
    assert hlo_analysis.instruction_counts(hlo).get("sort", 0) == 0
    assert 'custom_call_target="TopK"' not in hlo
    [plane] = run.state.d
    d = plane[0].size
    kb = sparsifier.num_kept(d, 0.2)
    for line in hlo.splitlines():
        if re.search(r"\s(gather|scatter)\(", line):
            dims = {int(v) for v in re.findall(r"\d+", line.split("=")[1]
                                                .split("(")[0])}
            assert not dims & {d, kb}, line


def test_banner_reports_own_side_and_topk_draws(one_node_run):
    _, out = one_node_run
    [banner] = [ln for ln in out.splitlines() if ln.startswith("arch=")]
    assert "own_sdm=mask-select compact_draws=0" in banner, banner
    assert "gossip_rounds=0" in banner
