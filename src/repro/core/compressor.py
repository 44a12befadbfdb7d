"""Pluggable Compressor layer: what actually goes on the wire.

The paper's thesis is that the WIRE PAYLOAD — sparse differential
Gaussian-masked messages — is the single lever for both privacy and
communication efficiency. This module makes that payload a first-class
object: a small registry of compressors, each defining

    compress(key, x, node=...) -> Payload     # what a node transmits
    decompress(payload)        -> x_hat       # what a receiver rebuilds
    wire_elements / wire_bits  -> int         # exact cost accounting

where ``Payload`` is a SHAPE-STATIC pytree (values + indices + scale)
that ``gossip.exchange_payload`` can ppermute generically — no
hand-packed flat buffers per call site. Static shapes are what make the
payload a legal `collective-permute` operand; heterogeneous per-node
sparsity budgets therefore pad to the max-k across nodes (rows beyond a
node's own k carry zero values, so scatter-adding them is a no-op).

Registered families (``make`` parses CLI-style specs):

    bernoulli        paper-faithful i.i.d. Bernoulli(p) masking; dense
                     tensor on the wire, expected p*d informative coords.
    fixedk           seed-synchronized fixed-k packing: exactly
                     k = ceil(p*d) coordinates, padded to max-k when p is
                     a per-node tuple.
    block / block:B  fixed-k at B-coordinate block granularity (DMA-
                     friendly; required beyond 2^31-element leaves).
    rows             fixed-k over trailing-dim rows (keeps each leaf's
                     tensor-parallel sharding intact — the production
                     SDM mode).
    qsgd / qsgd:b    QSGD-style stochastic quantizer (Alistarh et al.;
                     cf. Layered Randomized Quantization, arXiv:2312.07060):
                     per-leaf l2 norm + b-bit stochastic levels in int8.
                     Every coordinate ships, but at b bits instead of 32.

Accounting conventions: ``wire_elements`` counts INFORMATIVE non-zero
elements (the paper's Fig-3 "non-zero digits" metric; pad rows excluded).
``wire_bits`` charges value bits plus, for packed formats, the index
side-channel at ceil(log2 d) bits per kept element — pass
``index_sync=True`` when both endpoints regenerate index sets from a
shared seed (the repo's gossip transport), which removes index traffic.
``release_probability`` is what the RDP accountant needs: the per-
coordinate probability that a coordinate of the masked message is
released at all (1.0 for quantizers — they release every coordinate).
"""
from __future__ import annotations

import dataclasses
import math
from fractions import Fraction
from typing import Any, Callable, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparsifier

__all__ = [
    "Payload",
    "Compressor",
    "BernoulliCompressor",
    "FixedKCompressor",
    "RowsCompressor",
    "QSGDCompressor",
    "FusedQSGDCompressor",
    "make",
    "names",
    "register",
    "index_bits",
    "node_mean_exact",
    "tree_wire_elements",
    "tree_wire_bits",
    "tree_wire_elements_exact",
    "tree_wire_bits_exact",
]


def index_bits(d: int) -> int:
    """Bits to address one of d coordinates: ceil(log2 d) (0 for d <= 1)."""
    return max(0, math.ceil(math.log2(d))) if d > 1 else 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Payload:
    """Shape-static wire format: the pytree a node actually transmits.

    ``values`` is the packed/masked/quantized data, ``indices`` the
    explicit coordinate side-channel (None when dense or implicit via
    seed regeneration), ``scale`` an optional per-payload scalar (e.g.
    the QSGD norm). ``shape`` and ``meta`` are STATIC aux data (identical
    on every node) so the payload can cross `jax.lax.ppermute` leaf by
    leaf and be decompressed on the receiver without renegotiation.
    """

    values: Any
    indices: Any = None
    scale: Any = None
    shape: Tuple[int, ...] = ()
    meta: Tuple = ()

    def tree_flatten(self):
        return (self.values, self.indices, self.scale), (self.shape, self.meta)

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, indices, scale = children
        return cls(values=values, indices=indices, scale=scale,
                   shape=aux[0], meta=aux[1])


def _as_p_tuple_or_float(p):
    if isinstance(p, (list, tuple)):
        p = tuple(float(v) for v in p)
        if not p:
            raise ValueError("per-node p must be non-empty")
        if any(not (0.0 < v <= 1.0) for v in p):
            raise ValueError("every per-node p must be in (0, 1]")
        return p
    if not (0.0 < float(p) <= 1.0):
        raise ValueError(f"p must be in (0, 1], got {p}")
    return float(p)


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Base: a transmit-probability-parameterized compressor.

    Frozen/hashable — safe to close over in jit/shard_map. ``p`` may be
    a per-node tuple; ``compress(..., node=i)`` then resolves node i's
    budget (``node`` may be a traced index).
    """

    p: "float | Tuple[float, ...]" = 0.2

    # How SDM-DSGD moves this family's wire planes
    # (``sdm_dsgd._plane_exchange`` dispatches on it, once): "payload" is
    # the generic ``gossip.exchange_payload`` transport of compress()'s
    # pytree, which any new family rides unchanged; "dense" ppermutes the
    # decompressed plane; "blocks" and "rows" are the packed fixed-k
    # transports, whose receivers regenerate the index lists from the
    # seed so only the kept values cross the wire.
    transport: ClassVar[str] = "payload"
    # The family's ``SDMConfig.mode`` spelling (legacy; printed and
    # matched on by callers, derived from the resolved compressor).
    sdm_mode: ClassVar[str] = "payload"

    def __post_init__(self) -> None:
        object.__setattr__(self, "p", _as_p_tuple_or_float(self.p))

    # -- per-node budget helpers ------------------------------------------
    @property
    def p_max(self) -> float:
        return max(self.p) if isinstance(self.p, tuple) else self.p

    @property
    def p_min(self) -> float:
        return min(self.p) if isinstance(self.p, tuple) else self.p

    def p_of(self, node):
        """Transmit probability of ``node`` (traceable gather for tuples)."""
        if isinstance(self.p, tuple):
            if node is None:
                raise ValueError(
                    f"{self.name}: per-node p needs an explicit node=")
            return jnp.asarray(self.p, jnp.float32)[node]
        return self.p

    @property
    def release_probability(self):
        """Per-coordinate release probability for the RDP accountant.

        Sparsifiers release a coordinate w.p. p (Theorem 1's factor);
        quantizers release every coordinate (override with 1.0).
        """
        return self.p

    # -- interface ---------------------------------------------------------
    name: str = dataclasses.field(default="", init=False, repr=False)

    def compress(self, key: jax.Array, x: jax.Array, *, node=None) -> Payload:
        raise NotImplementedError

    def decompress(self, payload: Payload) -> jax.Array:
        raise NotImplementedError

    def wire_elements(self, shape: Tuple[int, ...], node: int | None = None
                      ) -> int:
        """Informative non-zero elements per transmission of one leaf."""
        raise NotImplementedError

    def wire_bits(self, shape: Tuple[int, ...], *, value_bits: int = 32,
                  index_sync: bool = False, node: int | None = None) -> int:
        """Exact wire bits per transmission of one leaf.

        Packed formats charge ``index_bits(d)`` per kept element unless
        ``index_sync`` (seed-regenerated index sets, no index traffic).
        """
        raise NotImplementedError

    # -- static-accounting helpers ----------------------------------------
    def _p_static(self, node: int | None) -> float:
        """Python-float budget for host-side accounting (worst node when
        p is a tuple and no node is named)."""
        if isinstance(self.p, tuple):
            return self.p[node] if node is not None else self.p_max
        return self.p

    # Exact (possibly fractional) per-leaf expectations, so tree-level
    # accounting rounds ONCE over the whole tree instead of per leaf
    # (round(p*d_total), the paper's Fig-3 convention) — deterministic
    # compressors just return their integer counts; probabilistic ones
    # return exact ``Fraction``s (p parsed via repr, so 0.3 * d is 3d/10
    # and cross-node means in het-p accounting cannot drift by float
    # rounding).
    def wire_elements_exact(self, shape, node=None) -> "Fraction | float":
        return float(self.wire_elements(shape, node=node))

    def wire_bits_exact(self, shape, *, value_bits=32, index_sync=False,
                        node=None) -> "Fraction | float":
        return float(self.wire_bits(shape, value_bits=value_bits,
                                    index_sync=index_sync, node=node))

    # -- sensitivity-transfer declaration ---------------------------------
    def coord_sensitivity_transfer(self, beta: float,
                                   shape: Tuple[int, ...]) -> float:
        """Worst-case coordinate bound after a compress -> decompress
        roundtrip of a tensor whose coordinates are bounded by ``beta``.

        The privacy certifier (``repro.analysis.sensitivity``) consumes
        this declaration: unbiased compressors inflate magnitudes (the
        1/p rescale, QSGD's norm-coupled levels), and the certificate
        records by how much so the released-value range is a proved
        constant, not folklore. Families that do not declare a transfer
        are conservatively unbounded — a new compressor MUST override
        this to enter the audited matrix (analyzer contract).
        ``tests/test_sensitivity_domain.py`` property-checks each
        declaration against the concrete roundtrip.
        """
        del shape
        return math.inf if beta > 0.0 else 0.0


# ==========================================================================
# Bernoulli (the paper's Definition-2 sparsifier; dense payload).
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class BernoulliCompressor(Compressor):
    """S(x): keep each coordinate w.p. p, scale kept by 1/p (Definition 2).

    The payload is the dense masked tensor — what the paper's theory
    analyses. Wire accounting counts the expected p*d informative
    coordinates (a sparse encoding would ship value + index per nnz).
    """

    name: str = dataclasses.field(default="bernoulli", init=False, repr=False)
    transport: ClassVar[str] = "dense"
    sdm_mode: ClassVar[str] = "bernoulli"

    def compress(self, key, x, *, node=None) -> Payload:
        vals = sparsifier.bernoulli_sparsify(key, x, self.p_of(node)
                                             if isinstance(self.p, tuple)
                                             else self.p)
        return Payload(values=vals, shape=tuple(x.shape),
                       meta=("bernoulli",))

    def decompress(self, payload: Payload) -> jax.Array:
        return payload.values

    def wire_elements_exact(self, shape, node=None) -> Fraction:
        return Fraction(repr(self._p_static(node))) * math.prod(shape)

    def wire_elements(self, shape, node=None) -> int:
        return int(round(self.wire_elements_exact(shape, node)))

    def wire_bits_exact(self, shape, *, value_bits=32, index_sync=False,
                        node=None) -> Fraction:
        d = int(math.prod(shape))
        per = value_bits + (0 if index_sync else index_bits(d))
        return self.wire_elements_exact(shape, node) * per

    def wire_bits(self, shape, *, value_bits=32, index_sync=False,
                  node=None) -> int:
        return int(round(self.wire_bits_exact(
            shape, value_bits=value_bits, index_sync=index_sync, node=node)))

    def coord_sensitivity_transfer(self, beta, shape):
        # kept coordinates are rescaled by 1/p; the sparsest node's
        # budget is the worst case under per-node p.
        del shape
        return beta / self.p_min


# ==========================================================================
# Fixed-k packing (element blocks); the pad-to-max-k payload format.
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class FixedKCompressor(Compressor):
    """Exactly k = ceil(p * n_blocks) blocks, packed (values, indices).

    With per-node p the payload pads to k_max = max_i k_i: every node
    draws k_max top-k block indices from its seed, zeroes the value rows
    beyond its own k_i, and scales kept rows by n_blocks/k_i. Indices are
    distinct (top-k), so scatter-adding the zero pad rows is a no-op and
    the SAME static payload shape serves every node — the property the
    ppermute transport requires (ROADMAP's "heterogeneous p in fixed-k
    modes" item).
    """

    block: int = 1
    name: str = dataclasses.field(default="fixedk", init=False, repr=False)
    transport: ClassVar[str] = "blocks"
    sdm_mode: ClassVar[str] = "fixedk_packed"

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.block < 1:
            raise ValueError("block must be >= 1")

    def _k_table(self, nb: int):
        if isinstance(self.p, tuple):
            return tuple(sparsifier.num_kept(nb, pi) for pi in self.p)
        return None

    def k_max(self, nb: int) -> int:
        kt = self._k_table(nb)
        return max(kt) if kt else sparsifier.num_kept(nb, self.p)

    def _block_view(self, x: jax.Array) -> jax.Array:
        return sparsifier.block_view(x.reshape(-1), self.block)

    def compress(self, key, x, *, node=None) -> Payload:
        xb = self._block_view(x)
        nb = xb.shape[0]
        kt = self._k_table(nb)
        kmax = self.k_max(nb)
        idx = sparsifier.fixedk_indices(key, nb, kmax)
        vals = jnp.take(xb, idx, axis=0)
        if kt is None:
            vals = vals * (nb / kmax)
        else:
            if node is None:
                raise ValueError("per-node p needs node=")
            kb = jnp.asarray(kt, jnp.int32)[node]
            keep = (jnp.arange(kmax) < kb)[:, None]
            vals = vals * (nb / kb.astype(jnp.float32)) \
                * keep.astype(vals.dtype)
        return Payload(values=vals.astype(xb.dtype), indices=idx,
                       shape=tuple(x.shape), meta=("fixedk", self.block))

    def decompress(self, payload: Payload) -> jax.Array:
        block = payload.meta[1]
        d = int(math.prod(payload.shape))
        nb = -(-d // block)
        out = jnp.zeros((nb, block), payload.values.dtype)
        # .add (not .set): pad rows and ppermute-zeroed payloads carry
        # zero values at possibly colliding indices — adding is a no-op.
        out = out.at[payload.indices].add(payload.values)
        return out.reshape(-1)[:d].reshape(payload.shape)

    def wire_elements(self, shape, node=None) -> int:
        d = int(math.prod(shape))
        nb = -(-d // self.block)
        kb = sparsifier.num_kept(nb, self._p_static(node))
        return min(kb * self.block, d)   # pad coords are never payload

    def wire_bits(self, shape, *, value_bits=32, index_sync=False,
                  node=None) -> int:
        d = int(math.prod(shape))
        nb = -(-d // self.block)
        kb = sparsifier.num_kept(nb, self._p_static(node))
        bits = min(kb * self.block, d) * value_bits
        if not index_sync:
            bits += kb * index_bits(nb)
        return bits

    def coord_sensitivity_transfer(self, beta, shape):
        # kept blocks are rescaled by nb/kb; min-p (fewest kept blocks)
        # maximizes the rescale. Distinct top-k indices mean scatter-add
        # never stacks two kept blocks on one coordinate.
        d = int(math.prod(shape))
        nb = -(-d // self.block)
        kb = sparsifier.num_kept(nb, self.p_min)
        return beta * nb / kb


@dataclasses.dataclass(frozen=True)
class RowsCompressor(Compressor):
    """Fixed-k over trailing-dim rows: blocks = whole rows of the leaf.

    Equivalent to ``FixedKCompressor(block=leaf.shape[-1])`` per leaf,
    but resolved from each leaf's own shape so every packed row keeps the
    leaf's model-axis sharding (the production fixedk_rows mode).
    """

    name: str = dataclasses.field(default="rows", init=False, repr=False)
    transport: ClassVar[str] = "rows"
    sdm_mode: ClassVar[str] = "fixedk_rows"

    def _rows_cols(self, shape: Tuple[int, ...]) -> Tuple[int, int]:
        d = int(math.prod(shape))
        cols = shape[-1] if len(shape) > 1 else 1
        return d // cols, cols

    def compress(self, key, x, *, node=None) -> Payload:
        rows, cols = self._rows_cols(tuple(x.shape))
        xb = x.reshape(rows, cols)
        if isinstance(self.p, tuple):
            raise ValueError("rows compressor does not support per-node p "
                             "(use fixedk/block for pad-to-max-k payloads)")
        kb = sparsifier.num_kept(rows, self.p)
        idx = sparsifier.fixedk_indices(key, rows, kb)
        vals = jnp.take(xb, idx, axis=0) * (rows / kb)
        return Payload(values=vals.astype(xb.dtype), indices=idx,
                       shape=tuple(x.shape), meta=("rows",))

    def decompress(self, payload: Payload) -> jax.Array:
        rows, cols = self._rows_cols(payload.shape)
        out = jnp.zeros((rows, cols), payload.values.dtype)
        out = out.at[payload.indices].add(payload.values)
        return out.reshape(payload.shape)

    def wire_elements(self, shape, node=None) -> int:
        rows, cols = self._rows_cols(tuple(shape))
        return sparsifier.num_kept(rows, self._p_static(node)) * cols

    def wire_bits(self, shape, *, value_bits=32, index_sync=False,
                  node=None) -> int:
        rows, cols = self._rows_cols(tuple(shape))
        kb = sparsifier.num_kept(rows, self._p_static(node))
        bits = kb * cols * value_bits
        if not index_sync:
            bits += kb * index_bits(rows)
        return bits

    def coord_sensitivity_transfer(self, beta, shape):
        rows, _ = self._rows_cols(tuple(shape))
        kb = sparsifier.num_kept(rows, self.p_min)
        return beta * rows / kb


# ==========================================================================
# QSGD-style stochastic quantizer (second compressor family).
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class QSGDCompressor(Compressor):
    """Q(x): per-leaf l2 norm + stochastic b-bit levels (sign-magnitude).

    With s = 2^(b-1) - 1 levels, coordinate x_i maps to
    ``sign(x_i) * round_stoch(|x_i| * s / ||x||)``, and decompresses to
    ``||x|| / s * q_i`` — unbiased (E[Q(x)] = x), like the Bernoulli
    sparsifier, so it slots behind the same interface. Every coordinate
    ships (release probability 1 for the accountant) but at b value bits
    instead of 32.

    Wire realization: b = 8 ships int8 (a 4x byte cut in HLO); SUB-BYTE
    levels (b in {2, 4}) are offset-encoded (level + s, in [0, 2s] <
    2^b) and PACKED 8/b per uint8 lane, so the HLO payload bytes
    actually shrink to ceil(d * b / 8) — the accounting's exact-b-bits
    charge is realized on the wire, closing ROADMAP's sub-byte item.
    Odd widths (3/5/6/7) keep the unpacked int8 payload. ``p`` is
    unused by the mechanism and kept only so quantizers share the
    registry construction path.
    """

    bits: int = 8
    name: str = dataclasses.field(default="qsgd", init=False, repr=False)
    sdm_mode: ClassVar[str] = "qsgd"

    def __post_init__(self) -> None:
        super().__post_init__()
        if not 2 <= self.bits <= 8:
            raise ValueError("qsgd bits must be in [2, 8] (int8 wire)")

    @property
    def levels(self) -> int:
        return 2 ** (self.bits - 1) - 1

    @property
    def pack_factor(self) -> int:
        """Levels per uint8 wire lane (1 = unpacked int8)."""
        return 8 // self.bits if self.bits in (2, 4) else 1

    @property
    def release_probability(self):
        return 1.0   # every coordinate is released at every step

    def compress(self, key, x, *, node=None) -> Payload:
        s = float(self.levels)
        xf = x.astype(jnp.float32)
        norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
        ratio = jnp.abs(xf) * (s / jnp.maximum(norm, 1e-30))
        level = jnp.floor(ratio)
        frac = ratio - level
        level = level + (jax.random.uniform(key, x.shape) < frac)
        q = (jnp.sign(xf) * jnp.minimum(level, s)).astype(jnp.int32)
        k = self.pack_factor
        if k == 1:
            return Payload(values=q.astype(jnp.int8), scale=norm,
                           shape=tuple(x.shape), meta=("qsgd", self.bits))
        # offset-encode to [0, 2s] (< 2^bits) and pack k levels per u8.
        off = (q + int(s)).reshape(-1)
        pad = (-off.shape[0]) % k
        if pad:
            off = jnp.pad(off, (0, pad))
        groups = off.reshape(-1, k)
        byte = jnp.zeros((groups.shape[0],), jnp.int32)
        for j in range(k):
            byte = byte | (groups[:, j] << (j * self.bits))
        return Payload(values=byte.astype(jnp.uint8), scale=norm,
                       shape=tuple(x.shape),
                       meta=("qsgd", self.bits, "u8pack"))

    def decompress(self, payload: Payload) -> jax.Array:
        bits = payload.meta[1]
        s = float(2 ** (bits - 1) - 1)
        if len(payload.meta) > 2 and payload.meta[2] == "u8pack":
            k = 8 // bits
            mask = (1 << bits) - 1
            v = payload.values.astype(jnp.int32)          # (m,) bytes
            parts = [(v >> (j * bits)) & mask for j in range(k)]
            d = int(math.prod(payload.shape))
            flat = jnp.stack(parts, axis=1).reshape(-1)[:d] - int(s)
            q = flat.reshape(payload.shape).astype(jnp.float32)
        else:
            q = payload.values.astype(jnp.float32)
        return (payload.scale / s) * q

    def wire_elements(self, shape, node=None) -> int:
        return int(math.prod(shape))   # every coordinate ships

    def wire_bits(self, shape, *, value_bits=32, index_sync=False,
                  node=None) -> int:
        del value_bits, index_sync   # quantized values, no index channel
        d = int(math.prod(shape))
        if self.pack_factor > 1:     # u8-packed lanes: exact wire bytes
            return -(-d // self.pack_factor) * 8 + 32   # + the norm scalar
        return d * self.bits + 32

    def coord_sensitivity_transfer(self, beta, shape):
        # a decompressed coordinate is (||x||/s) * q with |q| <= s, so it
        # is bounded by the leaf l2 norm <= beta * sqrt(d): the quantizer
        # can concentrate the whole norm budget on one coordinate.
        return beta * math.sqrt(int(math.prod(shape)))


@dataclasses.dataclass(frozen=True)
class FusedQSGDCompressor(QSGDCompressor):
    """QSGD with the quantize+pack chain fused into ONE pallas launch
    and the norm EMBEDDED in the byte payload (single wire leaf).

    Same mechanism, same bits-on-the-wire accounting as ``qsgd`` — the
    stochastic levels are BIT-IDENTICAL (uniforms drawn at the canonical
    plane shape outside the kernel; see kernels/wire_compress) — but the
    wire format changes in two launch-count-relevant ways:

    * the multi-kernel XLA quantize/offset/shift-or chain collapses into
      one ``kernels.wire_compress.qsgd_pack`` pallas call per plane;
    * the f32 norm rides as 4 bitcast bytes appended to the value
      buffer, so the payload is ONE u8 leaf instead of (values, scale) —
      halving collective-permutes per gossip round. ``wire_bits`` is
      inherited unchanged: ceil(d/k)*8 + 32 packed (k = 8/bits) and
      d*8 + 32 for bits=8 are exactly the single-buffer byte count.

    bits=8 consequently ships OFFSET-encoded u8 (q + s) rather than
    int8; roundtrip values stay bit-identical to ``qsgd:8``. Odd widths
    have no exact byte image, hence bits in {2, 4, 8} only.
    """

    name: str = dataclasses.field(default="qsgdf", init=False, repr=False)
    sdm_mode: ClassVar[str] = "payload"   # its own wire, not qsgd's

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.bits not in (2, 4, 8):
            raise ValueError(
                "qsgdf bits must be in {2, 4, 8}: the fused single-buffer "
                "format needs an exact byte image")

    def compress(self, key, x, *, node=None) -> Payload:
        from repro.kernels import wire_compress   # lazy: core -> kernels
        xf = x.astype(jnp.float32)
        norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
        u = jax.random.uniform(key, x.shape)   # canonical-shape draw
        data = wire_compress.qsgd_pack(xf, u, norm, bits=self.bits)
        tail = jax.lax.bitcast_convert_type(norm, jnp.uint8)   # (4,) bytes
        return Payload(values=jnp.concatenate([data, tail]),
                       shape=tuple(x.shape), meta=("qsgdf", self.bits))

    def decompress(self, payload: Payload) -> jax.Array:
        bits = payload.meta[1]
        s = float(2 ** (bits - 1) - 1)
        v = payload.values
        # scalar-indexed little-endian reassembly of the f32 norm: stays
        # an elementwise graph the consumers can fuse (a (4,)u8 -> f32
        # bitcast lowers to its own reduce-style fusion on CPU).
        w32 = sum(v[i - 4].astype(jnp.uint32) << (8 * (i))
                  for i in range(4))
        norm = jax.lax.bitcast_convert_type(w32, jnp.float32)
        d = int(math.prod(payload.shape))
        k = 8 // bits if bits in (2, 4) else 1
        shape = payload.shape
        if k == 1:
            q = (v[:d].astype(jnp.int32) - int(s)).reshape(shape)
        elif len(shape) == 2 and shape[-1] % k == 0:
            # lane-aligned planes (the wire transport's only shape):
            # unpack AT the output shape via a broadcast shift — element
            # (r, c) is byte (r, c//k) >> ((c % k) * bits). Fuses into
            # one loop fusion with the scale multiply, unlike the
            # stack/reshape/slice chain of the generic path.
            rows, cols = shape
            b2 = v[:d // k].astype(jnp.int32).reshape(rows, cols // k)
            sh = jnp.asarray((np.arange(cols) % k) * bits, jnp.int32)
            rep = jnp.broadcast_to(b2[:, :, None],
                                   (rows, cols // k, k)).reshape(rows, cols)
            q = ((rep >> sh[None, :]) & ((1 << bits) - 1)) - int(s)
        else:
            mask = (1 << bits) - 1
            data = v[:-4].astype(jnp.int32)
            parts = [(data >> (j * bits)) & mask for j in range(k)]
            q = (jnp.stack(parts, axis=1).reshape(-1)[:d]
                 - int(s)).reshape(shape)
        return (norm / s) * q.astype(jnp.float32)


# ==========================================================================
# Registry + CLI spec parsing.
# ==========================================================================

_FAMILIES: Dict[str, Callable[..., Compressor]] = {}


def register(family: str, factory: Callable[..., Compressor]) -> None:
    """Register a compressor family under ``family`` (spec prefix)."""
    _FAMILIES[family] = factory


def names() -> Tuple[str, ...]:
    return tuple(sorted(_FAMILIES))


register("bernoulli", lambda p, arg=None: BernoulliCompressor(p=p))
register("fixedk", lambda p, arg=None: FixedKCompressor(
    p=p, block=int(arg) if arg else 1))
register("block", lambda p, arg=None: FixedKCompressor(
    p=p, block=int(arg) if arg else 128))
register("rows", lambda p, arg=None: RowsCompressor(p=p))
register("qsgd", lambda p, arg=None: QSGDCompressor(
    p=p, bits=int(arg) if arg else 8))
register("qsgdf", lambda p, arg=None: FusedQSGDCompressor(
    p=p, bits=int(arg) if arg else 4))


def make(spec: str, p: "float | Tuple[float, ...]" = 0.2) -> Compressor:
    """Parse a CLI compressor spec: ``family`` or ``family:<arg>``.

    ``bernoulli`` | ``fixedk`` | ``fixedk:<block>`` | ``block:<B>`` |
    ``rows`` | ``qsgd:<bits>``. ``p`` is the transmit budget (scalar or
    per-node tuple) for the sparsifying families.
    """
    spec = spec.strip().lower()
    family, _, arg = spec.partition(":")
    if family not in _FAMILIES:
        raise ValueError(
            f"unknown compressor {spec!r}; registered: {', '.join(names())}")
    return _FAMILIES[family](p, arg or None)


# ==========================================================================
# Tree-level accounting helpers.
# ==========================================================================

def node_mean_exact(p, per_node_fn) -> "Fraction | float":
    """Across-node EXACT mean of per-node accounting expectations.

    The het-p Fig-3 convention (network total = mean * n_nodes), shared
    by SDM and push-sum accounting: with a per-node ``p`` tuple the mean
    is taken over the UNrounded per-node values so the caller can fold
    in further exact factors (schedule degree) and round ONCE — a
    per-node round followed by a rounded mean can drift +-1 element from
    the tree-level round(p * d_total) convention. Scalar ``p`` calls
    ``per_node_fn(None)`` straight through.
    """
    if isinstance(p, tuple):
        vals = [per_node_fn(i) for i in range(len(p))]
        return sum(vals) / len(vals)
    return per_node_fn(None)


def tree_wire_elements_exact(comp: Compressor, params,
                             node: int | None = None) -> "Fraction | float":
    """UNrounded informative elements per step over a pytree.

    Fractional expectations (bernoulli) sum EXACTLY across leaves
    (Fractions); callers fold in any further exact factors (across-node
    het-p means, per-link schedule degree) before rounding ONCE.
    """
    return sum(comp.wire_elements_exact(tuple(x.shape), node=node)
               for x in jax.tree.leaves(params))


def tree_wire_elements(comp: Compressor, params, node: int | None = None
                       ) -> int:
    """Informative elements one node transmits per step over a pytree.

    Rounds the exact sum once — round(p * d_total), the paper's Fig-3
    convention — while packed/quantized counts are already integers.
    """
    return int(round(tree_wire_elements_exact(comp, params, node=node)))


def tree_wire_bits_exact(comp: Compressor, params, *, value_bits: int = 32,
                         index_sync: bool = False,
                         node: int | None = None) -> "Fraction | float":
    """UNrounded wire bits per step over a pytree (see elements variant)."""
    return sum(
        comp.wire_bits_exact(tuple(x.shape), value_bits=value_bits,
                             index_sync=index_sync, node=node)
        for x in jax.tree.leaves(params))


def tree_wire_bits(comp: Compressor, params, *, value_bits: int = 32,
                   index_sync: bool = False, node: int | None = None) -> int:
    """Exact wire bits one node transmits per step over a pytree."""
    return int(round(tree_wire_bits_exact(comp, params,
                                          value_bits=value_bits,
                                          index_sync=index_sync, node=node)))
