"""Serving engines: continuous batching (default) + static batch baseline.

``ServingEngine`` is a slot-based continuous-batching scheduler over the
paged KV cache (``kv_cache.py``): finished requests free their slot and
their pages, queued requests are admitted mid-flight (a single-request
prefill lands in the freed slot, decode resumes the next step), and the
decode step is ONE jitted function carrying a device-side done-mask and
token buffer — per-token host work is a single small done-mask poll; all
real bookkeeping (prefill, page alloc/free, output read-back) happens
only at admission/retirement boundaries.

``StaticServingEngine`` is the seed's static-batch engine kept as the
benchmark baseline, with its ragged-prompt bug FIXED: right-padded
unequal-length prompts now read each row's logits at its own last real
token and decode at per-row cache offsets / RoPE phases (causal masking
already isolates rows during prefill, so batched == one-at-a-time —
pinned in tests/test_serving_engine.py). Models with recurrent mixers
(mamba/rwkv) are grouped into equal-length sub-batches instead: a
recurrent state that has consumed right-padding cannot be repaired by
masking.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.serving.kv_cache import PagedKVCache

__all__ = ["Request", "ServingEngine", "StaticServingEngine", "ServeStats"]


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # filled by the engine:
    output: Optional[List[int]] = None
    ttft_s: Optional[float] = None     # submit -> first token available
    finish_s: Optional[float] = None   # submit -> retirement


@dataclasses.dataclass
class ServeStats:
    """Per-``serve()`` call instrumentation (consumed by serve_bench)."""
    wall_s: float = 0.0
    tokens: int = 0
    step_wall_s: List[float] = dataclasses.field(default_factory=list)
    step_tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_s: List[float] = dataclasses.field(default_factory=list)
    pages_peak: int = 0
    pages_dense_equiv: int = 0
    prefills: int = 0
    decode_steps: int = 0


class _DecodeState(NamedTuple):
    """Device-resident continuous-batching state (one row per slot)."""
    pages: Dict[str, Any]     # {period-slot -> (k_pages, v_pages)}
    rec: Dict[str, Any]       # {period-slot -> recurrent state (n, B, ...)}
    offsets: jax.Array        # (B,) tokens already cached per slot
    last_tok: jax.Array       # (B,) token to feed next
    out_buf: jax.Array        # (B, max_out) generated tokens
    n_out: jax.Array          # (B,)
    budget: jax.Array         # (B,) max_new_tokens per slot
    eos: jax.Array            # (B,) eos id or -1
    active: jax.Array         # (B,) bool: slot holds a live request
    done: jax.Array           # (B,) bool: finished, awaiting retirement


def _is_recurrent(cfg: ModelConfig) -> bool:
    return any(s.mixer not in ("attn", "attn_local") for s in cfg.period)


def _bucket(n: int, cap: int) -> int:
    """Next power-of-two prefill length (bounds jit retraces)."""
    return min(max(8, 1 << (n - 1).bit_length()), cap)


class ServingEngine:
    """Continuous-batching engine over a paged KV cache.

    ``n_pages`` sizes the shared page pool (default: the dense
    equivalent ``max_batch * ceil(max_seq/page_size)``; ragged traffic
    runs fine far below that — admission applies backpressure).
    ``use_flash`` routes decode attention through the paged flash
    kernel; ``None`` turns it on exactly on a TPU backend. The XLA
    gather path computes identical logits (tested) and is the fast path
    on CPU hosts, where the kernel only interprets. ``sync_every``
    decode steps run between done-mask polls.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 256, dtype=jnp.float32, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 use_flash: Optional[bool] = None, sync_every: int = 1):
        if cfg.prefix or any(s.mixer == "mla" for s in cfg.period):
            raise NotImplementedError(
                f"{cfg.name}: the paged cache has no latent attention (mla) "
                f"or leading layers; serve it through "
                f"transformer.prefill/decode_step")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.dtype = dtype
        self.page_size = page_size
        self.n_pages = n_pages
        self.use_flash = (jax.default_backend() == "tpu"
                          if use_flash is None else use_flash)
        self.sync_every = max(1, sync_every)
        self.recurrent = _is_recurrent(cfg)
        self.last_stats: Optional[ServeStats] = None

        self._attn_slots = [str(i) for i, s in enumerate(cfg.period)
                            if s.mixer in ("attn", "attn_local")]
        self._rec_slots = [str(i) for i, s in enumerate(cfg.period)
                           if s.mixer not in ("attn", "attn_local")]

        self._encode = jax.jit(
            lambda p, c: transformer.encode_context(p, cfg, c))

        def _prefill(p, toks, last_index, ctx):
            cache = transformer.init_cache(cfg, 1, toks.shape[1], dtype)
            logits, cache = transformer.prefill(p, cfg, toks, cache,
                                                context=ctx,
                                                last_index=last_index)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache.slots

        self._prefill = jax.jit(_prefill)

        def _step(p, st: _DecodeState, tables, ctx):
            emit = st.active & ~st.done
            logits, pages, rec = transformer.decode_step_paged(
                p, cfg, st.last_tok, st.pages, st.rec, tables, st.offsets,
                emit, context=ctx, use_flash=self.use_flash)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            rows = jnp.arange(st.out_buf.shape[0])
            idx = jnp.clip(st.n_out, 0, st.out_buf.shape[1] - 1)
            out_buf = st.out_buf.at[rows, idx].set(
                jnp.where(emit, nxt, st.out_buf[rows, idx]))
            n_out = st.n_out + emit
            done = st.done | (emit & ((nxt == st.eos) | (n_out >= st.budget)))
            return st._replace(pages=pages, rec=rec,
                               offsets=st.offsets + emit,
                               last_tok=jnp.where(emit, nxt, st.last_tok),
                               out_buf=out_buf, n_out=n_out, done=done)

        self._step = jax.jit(_step, donate_argnums=(1,))

        def _admit(st: _DecodeState, rec_new, slot, length, first_tok,
                   budget, eos):
            rec = jax.tree.map(lambda a, u: a.at[:, slot].set(u[:, 0]),
                               st.rec, rec_new)
            done0 = (budget <= 1) | (first_tok == eos)
            return st._replace(
                rec=rec,
                offsets=st.offsets.at[slot].set(length),
                last_tok=st.last_tok.at[slot].set(first_tok),
                out_buf=st.out_buf.at[slot].set(0).at[slot, 0].set(first_tok),
                n_out=st.n_out.at[slot].set(1),
                budget=st.budget.at[slot].set(budget),
                eos=st.eos.at[slot].set(eos),
                active=st.active.at[slot].set(True),
                done=st.done.at[slot].set(done0))

        self._admit_fn = jax.jit(_admit, donate_argnums=(0,))

        def _retire(st: _DecodeState, slot):
            return st._replace(active=st.active.at[slot].set(False),
                               done=st.done.at[slot].set(False),
                               offsets=st.offsets.at[slot].set(0))

        self._retire_fn = jax.jit(_retire, donate_argnums=(0,))

    # ---------------- serve ----------------

    def serve(self, requests: List[Request],
              context: Optional[jax.Array] = None) -> List[Request]:
        """Serve all requests with continuous batching; returns them with
        ``output`` (and timing fields) filled, in the original order."""
        if not requests:
            return requests
        t0 = time.monotonic()
        stats = ServeStats()
        B = self.max_batch
        max_out = max(r.max_new_tokens for r in requests)

        ctx1 = None
        if context is not None:
            ctx1 = self._encode(self.params, context[:1])
        ctx_b = None if ctx1 is None else jnp.broadcast_to(
            ctx1, (B,) + ctx1.shape[1:])

        kv = PagedKVCache(self.cfg, max_batch=B, max_seq=self.max_seq,
                          page_size=self.page_size, n_pages=self.n_pages,
                          dtype=self.dtype)
        rec0 = {}
        if self._rec_slots:
            slots = transformer.init_cache(self.cfg, B, 1, self.dtype).slots
            rec0 = {si: slots[si] for si in self._rec_slots}
        st = _DecodeState(
            pages=kv.pages, rec=rec0,
            offsets=jnp.zeros((B,), jnp.int32),
            last_tok=jnp.zeros((B,), jnp.int32),
            out_buf=jnp.zeros((B, max_out), jnp.int32),
            n_out=jnp.zeros((B,), jnp.int32),
            budget=jnp.ones((B,), jnp.int32),
            eos=jnp.full((B,), -1, jnp.int32),
            active=jnp.zeros((B,), bool),
            done=jnp.zeros((B,), bool))

        queue = deque(requests)
        submit = {id(r): t0 for r in requests}
        free = list(range(B - 1, -1, -1))
        live: Dict[int, Request] = {}

        def admit_ready() -> bool:
            return bool(queue) and bool(free) and \
                kv.can_admit(len(queue[0].prompt) +
                             queue[0].max_new_tokens)

        while queue or live:
            while admit_ready():
                req = queue.popleft()
                slot = free.pop()
                need = len(req.prompt) + req.max_new_tokens
                kv.alloc(slot, need)
                st = self._prefill_into(st, kv, slot, req, ctx1)
                live[slot] = req
                req.ttft_s = time.monotonic() - submit[id(req)]
                stats.ttft_s.append(req.ttft_s)
                stats.prefills += 1
            if not live:
                need = kv.pages_needed(len(queue[0].prompt) +
                                       queue[0].max_new_tokens)
                raise RuntimeError(
                    f"request needs {need} pages but the pool only has "
                    f"{kv.n_pages}; raise n_pages or max_seq")

            done_np = np.asarray(st.done & st.active)
            if not done_np.any():
                emit_n = int(np.asarray(st.active & ~st.done).sum())
                ts = time.monotonic()
                tables = kv.tables()
                for _ in range(self.sync_every):
                    st = self._step(self.params, st, tables, ctx_b)
                    stats.decode_steps += 1
                done_np = np.asarray(st.done & st.active)  # forces the step
                dt = time.monotonic() - ts
                stats.step_wall_s.append(dt)
                # exact for sync_every=1; a row finishing mid-window
                # overcounts by at most sync_every-1 tokens
                stats.step_tokens.append(emit_n * self.sync_every)

            for slot in np.nonzero(done_np)[0].tolist():
                req = live.pop(slot)
                n = int(st.n_out[slot])
                req.output = np.asarray(st.out_buf[slot][:n]).tolist()
                req.finish_s = time.monotonic() - submit[id(req)]
                kv.release(slot)
                st = self._retire_fn(st, slot)
                free.append(slot)

        kv.pages = st.pages  # final buffers back onto the manager
        stats.pages_peak = kv.peak_in_use
        stats.pages_dense_equiv = kv.dense_equivalent_pages()
        stats.tokens = sum(len(r.output) for r in requests)
        stats.wall_s = time.monotonic() - t0
        self.last_stats = stats
        return requests

    def _prefill_into(self, st: _DecodeState, kv: PagedKVCache, slot: int,
                      req: Request, ctx1) -> _DecodeState:
        L = len(req.prompt)
        if L < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # recurrent mixers must see the EXACT prompt (a right-padded
        # tail would contaminate their state); attention models prefill
        # at a power-of-two bucket to bound retraces — causal masking +
        # last_index keep the padded prefill exact.
        Lp = L if self.recurrent else _bucket(L, self.max_seq)
        toks = np.zeros((1, Lp), np.int32)
        toks[0, :L] = req.prompt
        first, slots_cache = self._prefill(
            self.params, jnp.asarray(toks),
            jnp.asarray([L - 1], jnp.int32), ctx1)
        # paged write happens against the CURRENT pool buffers
        kv.pages = st.pages
        kv.write_prompt(slot, {si: (slots_cache[si].k, slots_cache[si].v)
                               for si in self._attn_slots}, L)
        rec_new = {si: slots_cache[si] for si in self._rec_slots}
        st = st._replace(pages=kv.pages)
        return self._admit_fn(st, rec_new, slot, L, int(first[0]),
                              req.max_new_tokens,
                              -1 if req.eos_id is None else req.eos_id)


# --------------------------------------------------------------------------
# Static-batch baseline (seed engine, ragged bug fixed)
# --------------------------------------------------------------------------

class StaticServingEngine:
    """Static batches of ``max_batch``: prefill together, decode until
    EVERY row in the batch is finished, then start the next batch. Kept
    as the throughput baseline the continuous engine must beat
    (benchmarks/check_serve.py); per-token bookkeeping is host-side by
    design."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 256, dtype=jnp.float32):
        if cfg.prefix or any(s.mixer == "mla" for s in cfg.period):
            raise NotImplementedError(
                f"{cfg.name}: the paged cache has no latent attention (mla) "
                f"or leading layers; serve it through "
                f"transformer.prefill/decode_step")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.dtype = dtype
        self.recurrent = _is_recurrent(cfg)
        self.last_stats: Optional[ServeStats] = None
        self._prefill = jax.jit(
            lambda p, t, c, ctx, li: transformer.prefill(
                p, cfg, t, c, context=ctx, last_index=li))
        self._decode = jax.jit(
            lambda p, t, c, ctx, offs: transformer.decode_step(
                p, cfg, t, c, context=ctx, offsets=offs))
        self._encode = jax.jit(
            lambda p, ctx: transformer.encode_context(p, cfg, ctx))

    def serve(self, requests: List[Request],
              context: Optional[jax.Array] = None) -> List[Request]:
        """Serve requests in static batches of max_batch (recurrent-mixer
        models additionally split into equal-prompt-length groups)."""
        t0 = time.monotonic()
        stats = ServeStats()
        if self.recurrent:
            by_len: Dict[int, List[Request]] = {}
            for r in requests:
                by_len.setdefault(len(r.prompt), []).append(r)
            groups = [g for _, g in sorted(by_len.items())]
        else:
            groups = [requests]
        for group in groups:
            for i in range(0, len(group), self.max_batch):
                self._serve_batch(group[i:i + self.max_batch], context,
                                  t0, stats)
        stats.tokens = sum(len(r.output) for r in requests)
        stats.wall_s = time.monotonic() - t0
        self.last_stats = stats
        return requests

    def _serve_batch(self, batch: List[Request],
                     context: Optional[jax.Array], t0: float,
                     stats: ServeStats) -> None:
        b = len(batch)
        # right-pad prompts to the longest; track true lengths. Causal
        # masking keeps each row's prefix exact; the row's first token
        # reads at its OWN last real position and decode continues from
        # its OWN length (the seed engine conditioned on the padding).
        lens = np.array([len(r.prompt) for r in batch], np.int32)
        plen = int(lens.max())
        prompts = np.zeros((b, plen), np.int32)
        for i, r in enumerate(batch):
            prompts[i, :len(r.prompt)] = r.prompt
        max_new = max(r.max_new_tokens for r in batch)
        assert plen + max_new <= self.max_seq, "increase max_seq"

        ctx = None
        if context is not None:
            ctx = self._encode(self.params, context[:b])

        cache = transformer.init_cache(self.cfg, b, self.max_seq, self.dtype)
        logits, cache = self._prefill(self.params, jnp.asarray(prompts),
                                      cache, ctx,
                                      jnp.asarray(lens - 1))
        next_tok = jnp.argmax(logits, axis=-1)
        np.asarray(next_tok)              # first tokens now materialized
        ttft = time.monotonic() - t0
        stats.prefills += 1
        for r in batch:
            r.ttft_s = ttft
            stats.ttft_s.append(ttft)
        offsets = jnp.asarray(lens)
        outs = [[] for _ in range(b)]
        done = [False] * b
        for _ in range(max_new):
            emitted = 0
            for i in range(b):
                if not done[i]:
                    outs[i].append(int(next_tok[i]))
                    emitted += 1
                    r = batch[i]
                    if (r.eos_id is not None and outs[i][-1] == r.eos_id) or \
                            len(outs[i]) >= r.max_new_tokens:
                        done[i] = True
            if all(done):
                break
            ts = time.monotonic()
            logits, cache = self._decode(self.params, next_tok, cache, ctx,
                                         offsets)
            offsets = offsets + 1
            next_tok = jnp.argmax(logits, axis=-1)
            np.asarray(next_tok)
            stats.step_wall_s.append(time.monotonic() - ts)
            stats.step_tokens.append(emitted)
            stats.decode_steps += 1
        now = time.monotonic() - t0
        for r, o in zip(batch, outs):
            r.output = o
            r.finish_s = now
