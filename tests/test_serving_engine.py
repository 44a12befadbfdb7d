"""Continuous-batching serving: paged KV cache, flash decode, ingest.

Covers the serving tentpole's correctness surface:
  * paged-cache allocator invariants (disjoint ownership, trash page
    never allocated, exact free-list accounting) and no cross-slot data
    leakage after page recycling, property-tested over random
    admission/retirement schedules,
  * paged flash decode == naive paged reference == an independent numpy
    oracle, incl. sliding window, softcap, and empty (seq_len 0) rows,
  * THE ragged-prompt pin: batched serving of unequal-length prompts
    equals serving each request one-at-a-time (the seed's static engine
    conditioned shorter rows on their right-padding),
  * checkpoint ingest: consensus-average of a real decentralized train
    run's stacked replicas, push-sum de-bias, and greedy determinism
    across two engine instantiations of the ingested model.
"""
import math
import os
import random
from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.checkpoint import load_flat, save_checkpoint
from repro.models import transformer
from repro.serving import (PagedKVCache, Request, ServingEngine,
                           StaticServingEngine)
from repro.serving.ingest import ingest_checkpoint


def _cfg(name):
    return configs.get_smoke_config(name)


def _params(name, seed=0):
    cfg = _cfg(name)
    return cfg, transformer.init_params(jax.random.PRNGKey(seed), cfg)


def _ragged_requests(cfg, *, lens, budgets, seed=1):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=m, eos_id=None)
            for n, m in zip(lens, budgets)]


def _one_at_a_time(cfg, params, requests, max_seq):
    outs = []
    for r in requests:
        r1 = Request(prompt=list(r.prompt), max_new_tokens=r.max_new_tokens,
                     eos_id=r.eos_id)
        StaticServingEngine(cfg, params, max_batch=1,
                            max_seq=max_seq).serve([r1])
        outs.append(r1.output)
    return outs


# ---------------------------------------------------------------------------
# Paged-cache allocator invariants (property test over schedules).
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_paged_cache_alloc_free_invariants(seed):
    cfg = _cfg("phi3-medium-14b")
    rng = random.Random(seed)
    kv = PagedKVCache(cfg, max_batch=4, max_seq=32, page_size=4,
                      n_pages=rng.choice([10, 16, 32]))
    live = {}
    for _ in range(30):
        admit = rng.random() < 0.6 or not live
        if admit and len(live) < kv.max_batch:
            slot = rng.choice([s for s in range(kv.max_batch)
                               if s not in live])
            n_tok = rng.randint(1, kv.max_seq)
            if not kv.can_admit(n_tok):
                with pytest.raises(ValueError):
                    kv.alloc(slot, n_tok)
                continue
            kv.alloc(slot, n_tok)
            live[slot] = n_tok
            # double-alloc on an occupied slot must refuse
            with pytest.raises(ValueError):
                kv.alloc(slot, 1)
        elif live:
            slot = rng.choice(list(live))
            kv.release(slot)
            del live[slot]
            assert kv.owned(slot) == ()
            assert not np.asarray(kv._tables[slot]).any()

        # accounting: in-use == sum of per-slot charges, free+used == pool
        assert kv.pages_in_use() == sum(
            kv.pages_needed(n) for n in live.values())
        assert kv.pages_in_use() + len(kv._free) == kv.n_pages
        # ownership: page 0 never handed out, no page owned twice
        owned = [p for s in live for p in kv.owned(s)]
        assert 0 not in owned
        assert len(owned) == len(set(owned))
        # block tables point at owned pages only (rest at trash)
        for s, n in live.items():
            row = np.asarray(kv._tables[s])
            need = kv.pages_needed(n)
            assert set(row[:need]) == set(kv.owned(s))
            assert not row[need:].any()
    with pytest.raises(ValueError):
        kv.alloc(0 if 0 not in live else
                 next(s for s in range(4) if s not in live), kv.max_seq + 1)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_paged_cache_no_cross_slot_leakage_after_recycle(seed):
    """Each live slot reads back exactly the data written at its
    admission, no matter how many other slots were admitted/retired
    (and its pages recycled) in between."""
    cfg = _cfg("phi3-medium-14b")
    kv_h, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    rng = random.Random(seed)
    kv = PagedKVCache(cfg, max_batch=3, max_seq=16, page_size=4, n_pages=8)
    attn_slots = [si for si in kv.pages]
    live = {}          # slot -> (fill_value, length)
    fill = 0
    for _ in range(14):
        if (rng.random() < 0.6 or not live) and len(live) < kv.max_batch \
                and kv.can_admit(12):
            slot = rng.choice([s for s in range(kv.max_batch)
                               if s not in live])
            length = rng.randint(1, 12)
            kv.alloc(slot, length)
            fill += 1
            # padded prefill: the tail beyond `length` is junk that must
            # be routed to the trash page, never into owned pages
            Lp = length + rng.choice([0, 3])
            k = np.full((cfg.n_periods, 1, Lp, kv_h, hd), fill, np.float32)
            k[:, :, length:] = -99.0
            kv.write_prompt(slot, {si: (jnp.asarray(k), jnp.asarray(-k))
                                   for si in attn_slots}, length)
            live[slot] = (fill, length)
        elif live:
            slot = rng.choice(list(live))
            kv.release(slot)
            del live[slot]
        for slot, (val, length) in live.items():
            got = kv.gather_dense(slot, length)
            for si, (gk, gv) in got.items():
                assert np.all(np.asarray(gk) == val), \
                    f"slot {slot} k leaked (want fill {val})"
                assert np.all(np.asarray(gv) == -val)


# ---------------------------------------------------------------------------
# Flash decode == naive reference == independent numpy oracle.
# ---------------------------------------------------------------------------

def _numpy_paged_attention(q, k_pages, v_pages, tbl, seq_lens, window,
                           softcap):
    b, h, dh = q.shape
    _, kvh, page, _ = k_pages.shape
    group = h // kvh
    out = np.zeros_like(q, dtype=np.float64)
    for i in range(b):
        L = int(seq_lens[i])
        if L == 0:
            continue
        k = np.stack([k_pages[tbl[i, p // page], :, p % page]
                      for p in range(L)])          # (L, kvh, dh)
        v = np.stack([v_pages[tbl[i, p // page], :, p % page]
                      for p in range(L)])
        for hh in range(h):
            kvh_i = hh // group
            s = (k[:, kvh_i] @ q[i, hh]) / math.sqrt(dh)
            if softcap is not None:
                s = softcap * np.tanh(s / softcap)
            if window is not None:
                s[np.arange(L) <= (L - 1) - window] = -np.inf
            p_ = np.exp(s - s.max())
            out[i, hh] = (p_ / p_.sum()) @ v[:, kvh_i]
    return out


@pytest.mark.parametrize("window,softcap", [(None, None), (6, None),
                                            (None, 5.0), (6, 5.0)])
def test_flash_vs_naive_paged_decode_equivalence(window, softcap):
    from repro.kernels.flash_attn.decode import paged_attention
    rng = np.random.default_rng(3)
    b, kvh, group, dh, page, n_pages, n_blocks = 5, 2, 3, 32, 4, 24, 4
    h = kvh * group
    q = rng.normal(size=(b, h, dh)).astype(np.float32)
    k_pages = rng.normal(size=(n_pages + 1, kvh, page, dh)).astype(np.float32)
    v_pages = rng.normal(size=(n_pages + 1, kvh, page, dh)).astype(np.float32)
    # disjoint per-row page ownership, like the real allocator; trailing
    # blocks of short rows point at the trash page 0 (full of junk)
    perm = rng.permutation(np.arange(1, n_pages + 1))
    seq_lens = np.array([0, 1, 7, 16, 10], np.int32)
    tbl = np.zeros((b, n_blocks), np.int32)
    nxt = 0
    for i in range(b):
        need = -(-max(int(seq_lens[i]), 1) // page)
        tbl[i, :need] = perm[nxt:nxt + need]
        nxt += need

    oracle = _numpy_paged_attention(q, k_pages, v_pages, tbl, seq_lens,
                                    window, softcap)
    ref = paged_attention(jnp.asarray(q), jnp.asarray(k_pages),
                          jnp.asarray(v_pages), jnp.asarray(tbl),
                          jnp.asarray(seq_lens), window=window,
                          softcap=softcap, use_kernel=False)
    ker = paged_attention(jnp.asarray(q), jnp.asarray(k_pages),
                          jnp.asarray(v_pages), jnp.asarray(tbl),
                          jnp.asarray(seq_lens), window=window,
                          softcap=softcap, use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), oracle, atol=2e-5)
    np.testing.assert_allclose(np.asarray(ker), oracle, atol=2e-5)
    # empty row contributes exactly nothing on both paths
    assert not np.asarray(ref)[0].any() and not np.asarray(ker)[0].any()


# ---------------------------------------------------------------------------
# THE ragged pin: batched == one-at-a-time.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["phi3-medium-14b", "gemma2-2b"])
def test_ragged_batched_matches_one_at_a_time(arch):
    cfg, params = _params(arch)
    lens, budgets = (3, 9, 5, 12, 7), (6, 3, 8, 5, 4)
    want = _one_at_a_time(cfg, params,
                          _ragged_requests(cfg, lens=lens, budgets=budgets),
                          max_seq=64)
    engines = [
        StaticServingEngine(cfg, params, max_batch=5, max_seq=64),
        ServingEngine(cfg, params, max_batch=3, max_seq=64, page_size=4),
        ServingEngine(cfg, params, max_batch=3, max_seq=64, page_size=4,
                      use_flash=True),
    ]
    for eng in engines:
        reqs = _ragged_requests(cfg, lens=lens, budgets=budgets)
        eng.serve(reqs)
        assert [r.output for r in reqs] == want, type(eng).__name__
    # continuous engines ran genuinely paged: fewer pages than dense
    stats = engines[1].last_stats
    assert 0 < stats.pages_peak < stats.pages_dense_equiv


def test_ragged_recurrent_matches_one_at_a_time():
    """Recurrent mixers can't mask away right-padding (state pollution):
    the static engine groups equal lengths, the continuous engine
    prefills at exact length. Both must match sequential serving."""
    cfg, params = _params("rwkv6-3b")
    lens, budgets = (4, 7, 4, 9), (5, 3, 6, 4)
    want = _one_at_a_time(cfg, params,
                          _ragged_requests(cfg, lens=lens, budgets=budgets),
                          max_seq=48)
    for eng in (StaticServingEngine(cfg, params, max_batch=4, max_seq=48),
                ServingEngine(cfg, params, max_batch=2, max_seq=48,
                              page_size=8)):
        reqs = _ragged_requests(cfg, lens=lens, budgets=budgets)
        eng.serve(reqs)
        assert [r.output for r in reqs] == want, type(eng).__name__


def test_continuous_more_requests_than_slots_recycles():
    """Queue 3x the slot count with wildly uneven budgets: every request
    completes correctly through slot recycling, and the page pool stays
    within its (sub-dense) bound."""
    cfg, params = _params("phi3-medium-14b")
    lens = (3, 6, 2, 8, 4, 5)
    budgets = (12, 1, 7, 2, 9, 3)
    want = _one_at_a_time(cfg, params,
                          _ragged_requests(cfg, lens=lens, budgets=budgets),
                          max_seq=32)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=32, page_size=4,
                        n_pages=2 * (32 // 4))  # exactly 2 dense rows
    reqs = _ragged_requests(cfg, lens=lens, budgets=budgets)
    eng.serve(reqs)
    assert [r.output for r in reqs] == want
    assert eng.last_stats.pages_peak <= 2 * (32 // 4)


# ---------------------------------------------------------------------------
# Checkpoint ingest.
# ---------------------------------------------------------------------------

def test_ingest_consensus_and_deterministic_serving(tmp_path):
    """Real decentralized train run -> npz -> ingest: the served model is
    the replica mean, and two fresh engines decode it identically."""
    from repro.core import SDMConfig, topology
    from repro.data import TokenStream
    from repro.train.trainer import run_decentralized

    cfg, params = _params("phi3-medium-14b")
    n = 3
    stack = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), params)
    stream = TokenStream(vocab_size=cfg.vocab_size, batch=n * 2,
                         seq_len=16, seed=0)

    def one_loss(p, tokens, labels):
        logits, aux = transformer.forward(p, cfg, tokens)
        return transformer.lm_loss(logits, labels, cfg.vocab_size, aux)

    def grad_fn(ps, batch):
        toks, labs = batch
        losses, grads = jax.vmap(jax.value_and_grad(one_loss))(
            ps, toks, labs)
        return grads, jnp.mean(losses)

    def batches():
        t = 0
        while True:
            toks, labs = stream.batch_at(t)
            yield (jnp.asarray(toks).reshape(n, 2, -1),
                   jnp.asarray(labs).reshape(n, 2, -1))
            t += 1

    ck = str(tmp_path / "ck")
    run_decentralized(
        topo=topology.ring(n), algorithm="sdm-dsgd",
        sdm_cfg=SDMConfig(p=0.4, theta=0.3, gamma=0.05, sigma=0.0),
        params_stack=stack, grad_fn=grad_fn, batches=batches(),
        steps=3, checkpoint_dir=ck, checkpoint_every=3)

    served, report = ingest_checkpoint(ck, cfg)
    assert report.n_nodes == n and not report.debiased
    assert np.isfinite(report.max_disagreement)

    # oracle: plain mean over the stacked replicas, straight off the npz
    flat = load_flat(os.path.join(ck, "step_00000003.npz"))
    np.testing.assert_allclose(
        np.asarray(served["embed"]),
        flat["x/embed"].astype(np.float64).mean(axis=0), rtol=1e-6)

    reqs = lambda: _ragged_requests(cfg, lens=(5, 9, 3), budgets=(6, 4, 7))
    outs = []
    for _ in range(2):  # two independent instantiations
        rs = ServingEngine(cfg, served, max_batch=2, max_seq=32,
                           page_size=4).serve(reqs())
        outs.append([r.output for r in rs])
    assert outs[0] == outs[1]
    rs = StaticServingEngine(cfg, served, max_batch=3,
                             max_seq=32).serve(reqs())
    assert [r.output for r in rs] == outs[0]


def test_ingest_pushsum_debias_and_raw_params(tmp_path):
    """x_i = w_i * theta with varying w must de-bias back to theta
    exactly (zero disagreement); a raw params checkpoint ingests
    unchanged."""
    cfg, params = _params("phi3-medium-14b")
    n = 4
    w = np.array([0.5, 1.0, 1.5, 2.0], np.float32)
    State = namedtuple("State", ["x", "w", "step"])
    x = jax.tree.map(
        lambda p: jnp.asarray(w.reshape((n,) + (1,) * p.ndim) * p[None]),
        params)
    save_checkpoint(str(tmp_path / "ps"), 5,
                    State(x=x, w=jnp.asarray(w), step=jnp.asarray(5)))
    served, report = ingest_checkpoint(str(tmp_path / "ps"), cfg)
    assert report.debiased and report.n_nodes == n
    assert report.max_disagreement < 1e-6
    np.testing.assert_allclose(np.asarray(served["embed"]),
                               np.asarray(params["embed"]), atol=1e-6)

    save_checkpoint(str(tmp_path / "raw"), 1, params)
    served2, report2 = ingest_checkpoint(str(tmp_path / "raw"), cfg)
    assert report2.n_nodes == 1 and report2.prefix == ""
    for a, b in zip(jax.tree.leaves(served2), jax.tree.leaves(params)):
        assert jnp.array_equal(a, b)
