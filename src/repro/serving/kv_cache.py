"""Paged KV cache: fixed-size page pool + per-slot block tables.

The dense serving cache reserves ``max_batch x max_seq`` per layer no
matter how long requests actually run. Here KV storage is a pool of
fixed-size pages — one pool per attention period-slot, shaped
``(n_periods, n_pages, kv_heads, page_size, head_dim)`` — and each
request slot owns a BLOCK TABLE row mapping its logical block j to a
physical page id. A slot is charged exactly
``ceil((prompt + budget) / page_size)`` pages at admission and returns
them at retirement, so the pool sizes to the live token footprint, not
to ``max_batch x max_seq``.

Conventions:

* **page 0 is the trash page**: never allocated, and the decode step
  routes writes of finished / empty rows there (see
  ``layers.attention_decode_paged``). A freed slot's table row is reset
  to all-zeros, so a stale table can never alias a page that has been
  handed to another slot.
* the same physical page id indexes every layer's pool (the page axis
  is shared across ``n_periods`` and across period-slots), so one
  allocation covers the whole depth of the model.
* allocation is host-side (a simple LIFO free list — recycled pages are
  reused immediately, which the leak property-test exploits); the pools
  and tables live on device and flow through the jitted decode step.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

__all__ = ["PagedKVCache", "TRASH_PAGE"]

TRASH_PAGE = 0

# Each attention period-slot's pool is a plain ``(k_pages, v_pages)``
# tuple, both (n_periods, n_pages+1, kv_heads, page_size, head_dim): head-
# major, so one head's page is a (page_size, head_dim) tile for the paged
# flash-decode kernel.
# Plain tuples (not a NamedTuple) on purpose: the decode step returns
# plain tuples, and a pytree-type flip between host bookkeeping and the
# jitted step would force a retrace at every admit/retire boundary.


def _attn_slots(cfg: ModelConfig) -> List[str]:
    return [str(i) for i, s in enumerate(cfg.period)
            if s.mixer in ("attn", "attn_local")]


class PagedKVCache:
    """Host-side manager for the device page pools + block tables.

    ``n_pages`` counts usable pages EXCLUDING the trash page (the device
    arrays carry n_pages + 1 physical pages). The default pool is sized
    for a full dense reservation — callers running ragged traffic pass a
    smaller pool and rely on admission-time backpressure
    (``can_admit``)."""

    def __init__(self, cfg: ModelConfig, *, max_batch: int, max_seq: int,
                 page_size: int = 16, n_pages: Optional[int] = None,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.page_size = page_size
        self.n_blocks = math.ceil(max_seq / page_size)
        if n_pages is None:
            n_pages = max_batch * self.n_blocks
        self.n_pages = n_pages
        self.dtype = dtype
        kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        shape = (cfg.n_periods, n_pages + 1, kv, page_size, hd)
        self.pages: Dict[str, Tuple[jax.Array, jax.Array]] = {
            si: (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for si in _attn_slots(cfg)
        }
        self._tables = np.zeros((max_batch, self.n_blocks), np.int32)
        self._tables_dev: Optional[jax.Array] = None
        self._free: List[int] = list(range(n_pages, 0, -1))  # LIFO, 1-based
        self._owned: Dict[int, List[int]] = {}               # slot -> pages
        self.peak_in_use = 0

    # ---------------- allocation ----------------

    def pages_needed(self, n_tokens: int) -> int:
        return math.ceil(max(n_tokens, 1) / self.page_size)

    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= len(self._free)

    def alloc(self, slot: int, n_tokens: int) -> None:
        """Charge ``slot`` enough pages for ``n_tokens`` and build its
        table row. Raises if the pool is exhausted (check ``can_admit``)
        or the slot already holds pages."""
        if slot in self._owned:
            raise ValueError(f"slot {slot} already holds pages")
        need = self.pages_needed(n_tokens)
        if n_tokens > self.max_seq:
            raise ValueError(f"{n_tokens} tokens > max_seq {self.max_seq}")
        if need > len(self._free):
            raise ValueError(f"pool exhausted: need {need}, "
                             f"free {len(self._free)}")
        got = [self._free.pop() for _ in range(need)]
        self._owned[slot] = got
        row = np.zeros(self.n_blocks, np.int32)
        row[:need] = got
        self._tables[slot] = row
        self._tables_dev = None
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use())

    def release(self, slot: int) -> None:
        """Return ``slot``'s pages to the free list and zero its table
        row (all blocks point at the trash page again)."""
        got = self._owned.pop(slot, None)
        if got is None:
            return
        self._free.extend(reversed(got))
        self._tables[slot] = 0
        self._tables_dev = None

    def owned(self, slot: int) -> Tuple[int, ...]:
        return tuple(self._owned.get(slot, ()))

    def tables(self) -> jax.Array:
        """Device copy of the block tables (cached until the next
        alloc/release)."""
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self._tables)
        return self._tables_dev

    # ---------------- device writes / reads ----------------

    def write_prompt(self, slot: int, dense: Dict[str, Any],
                     length: int) -> None:
        """Scatter a prefilled DENSE cache into ``slot``'s pages.

        ``dense``: {period-slot -> KVCache-like (k, v)} with k/v shaped
        (n_periods, 1, L, kv_heads, head_dim) from a single-request
        prefill; only the first ``length`` positions are real — padded
        tail positions are routed to the trash page, so bucket-padded
        prefills stay page-clean.
        """
        if not dense:   # pure-recurrent model: nothing paged to write
            return
        Lp = next(iter(dense.values()))[0].shape[2]
        pos = np.arange(Lp)
        row = self._tables[slot]
        real = pos < length
        page_id = np.where(real, row[np.minimum(pos // self.page_size,
                                                self.n_blocks - 1)],
                           TRASH_PAGE)
        in_page = np.where(real, pos % self.page_size, 0)
        page_id = jnp.asarray(page_id, jnp.int32)
        in_page = jnp.asarray(in_page, jnp.int32)
        for si, (k_dense, v_dense) in dense.items():
            kp, vp = self.pages[si]
            self.pages[si] = (_scatter_prompt(kp, k_dense, page_id, in_page),
                              _scatter_prompt(vp, v_dense, page_id, in_page))

    def gather_dense(self, slot: int, length: int) -> Dict[str, Any]:
        """Debug/test read-back: ``slot``'s first ``length`` cached
        tokens as dense (n_periods, length, kv, hd) arrays per layer."""
        row = self._tables[slot]
        pos = np.arange(length)
        page_id = jnp.asarray(row[pos // self.page_size], jnp.int32)
        in_page = jnp.asarray(pos % self.page_size, jnp.int32)
        out = {}
        for si, (kp, vp) in self.pages.items():
            # advanced indices split by a slice lead: (length, n_periods, ..)
            out[si] = (jnp.swapaxes(kp[:, page_id, :, in_page], 0, 1),
                       jnp.swapaxes(vp[:, page_id, :, in_page], 0, 1))
        return out

    def dense_equivalent_pages(self) -> int:
        """What a dense max_batch x max_seq reservation costs, in pages."""
        return self.max_batch * self.n_blocks


@jax.jit
def _scatter_prompt(pages: jax.Array, dense: jax.Array, page_id: jax.Array,
                    in_page: jax.Array) -> jax.Array:
    # pages (n_periods, n_pages+1, kv, P, hd); dense (n_periods, 1, L, kv, hd).
    # The advanced indices are split by a slice, so the indexed view is
    # (L, n_periods, kv, hd).
    return pages.at[:, page_id, :, in_page].set(
        jnp.swapaxes(dense[:, 0], 0, 1))
