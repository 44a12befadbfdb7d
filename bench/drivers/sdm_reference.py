"""Plain SDM-DSGD (the paper's Algorithm 1) over node-stacked states, the
yardstick the training cells are compared with.

Per node i and step t, with W the ring's mixing matrix:

    x_i += S_i(d_i)                         S: fixed-k sparsifier, key (i, t)
    s_i += sum_{j != i} W_ij S_j(d_j)       running weighted neighbour sum
    g_i  = clip(grad f(x_i), C) + sigma * eta_i     eta: Gaussian, key (i, t)
    d_i  = (1 - theta) x_i + theta (W_ii x_i + s_i - gamma g_i) - x_i

with s_i(0) = (1 - W_ii) x(0) and d(0) = 0. The sparsifier keeps
k = ceil(p * n_blocks) blocks of ``block`` consecutive coordinates of
the parameters laid end to end (leaves in flatten order, zero-padded to
a multiple of 128), drawn as the top k of uniform scores, and scales
them by n_blocks / k. The random keys follow the seed-synchronised
schedule the paper's transport needs: every endpoint regenerates
sender j's draw from (base key, j, t).

Each node's state lives on its own device (``devices[i]``); neighbour
payloads are copied across. Everything is float32 at ``highest``
precision, or, for the control, with every tensor the system keeps in
bfloat16 (parameters, gradient, noise) rounded to scaled float8.
Nothing here imports the system under test.
"""
from __future__ import annotations

import decimal
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np

LANE = 128
NOISE_SALT = 0x5EED
FP8_MAX = 240.0     # largest finite value of IEEE-style e4m3 (reduce_precision)


def num_kept(n: int, p: float) -> int:
    """k = ceil(p * n) in exact arithmetic, at least 1, at most n."""
    return min(n, max(1, math.ceil(Fraction(decimal.Decimal(repr(p))) * n)))


def ring_weights(n: int) -> np.ndarray:
    """Symmetric ring, weight 1/3 on self and on each neighbour."""
    if n == 1:
        return np.ones((1, 1))
    w = np.eye(n) / 3.0
    for i in range(n):
        w[i, (i + 1) % n] += 1.0 / 3.0
        w[i, (i - 1) % n] += 1.0 / 3.0
    return w


def round_fp8(tree):
    """Each leaf rounded to float8 (4 exponent and 3 mantissa bits) under a
    per-leaf scale. ``reduce_precision`` and not a cast there and back:
    XLA may drop a pair of converts as excess precision, and on the TPU
    it does, which leaves the control unrounded."""
    def one(a):
        a = a.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
        return jax.lax.reduce_precision(a / scale, exponent_bits=4,
                                        mantissa_bits=3) * scale
    return jax.tree.map(one, tree)


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


def probe_dots(probe, g, noise):
    """Per leaf, <probe - noise, g> with ``probe`` the leaves of a tree
    like ``g`` laid end to end (and any padding after them)."""
    out, off = [], 0
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(noise)):
        seg = probe[off:off + a.size].astype(jnp.float32)
        out.append(jnp.sum((seg - b.reshape(-1)) * a.reshape(-1)))
        off += a.size
    return jnp.stack(out)


def _flat(tree):
    v = jnp.concatenate([a.reshape(-1).astype(jnp.float32)
                         for a in jax.tree.leaves(tree)])
    return jnp.pad(v, (0, (-v.shape[0]) % LANE))


def _unflat(v, like):
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for a in leaves:
        out.append(v[off:off + a.size].reshape(a.shape))
        off += a.size
    return jax.tree.unflatten(treedef, out)


def sparsify(tree, key, p: float, block: int):
    """S(d): the fixed-k sparsified tree (dense, zeros off the kept set)."""
    v = _flat(tree)
    view = v.reshape(-1, block)
    nb = view.shape[0]
    kb = num_kept(nb, p)
    _, idx = jax.lax.top_k(jax.random.uniform(key, (nb,)), kb)
    kept = jnp.zeros_like(view).at[idx].set(
        jnp.take(view, idx, axis=0) * (nb / kb))
    return _unflat(kept.reshape(-1), tree)


class SDMReference:
    """Stacked SDM-DSGD over ``len(devices)`` nodes.

    ``loss_fn(params, tokens, labels)`` is the model's plain loss, at
    ``highest`` matmul precision.
    ``fault`` plants one of the faults the harness must catch, for the
    readings that set the comparison's limits: ``"half"`` takes each
    step's loss and gradient over the first half of the tokens only,
    ``"no_exchange"`` drops every neighbour payload.
    """

    def __init__(self, loss_fn, *, p, theta, gamma, sigma, clip_c, block,
                 devices, base_key, control=False, fault=None):
        self.n = len(devices)
        self.devices = devices
        self.w = ring_weights(self.n)
        self.p, self.theta, self.gamma = p, theta, gamma
        self.sigma, self.clip_c, self.block = sigma, clip_c, block
        self.base_key = base_key
        self.fault = fault
        q = round_fp8 if control else (lambda t: t)
        self._q = q
        half = fault == "half"

        def value_and_grad(x, tokens, labels):
            if half:
                s = tokens.shape[-1] // 2
                tokens, labels = tokens[..., :s], labels[..., :s]
            return jax.value_and_grad(loss_fn)(x, tokens, labels)

        def mask(g, key):
            """clip(g, C) and the Gaussian mask sigma * eta, apart."""
            g = q(jax.tree.map(lambda a: jnp.clip(a, -clip_c, clip_c), g))
            leaves, treedef = jax.tree.flatten(g)
            noise = q(jax.tree.unflatten(treedef, [
                sigma * jax.random.normal(jax.random.fold_in(key, i), a.shape)
                for i, a in enumerate(leaves)]))
            return g, noise

        def commit(x, s, g, noise, sw):
            ghat = jax.tree.map(jnp.add, g, noise)
            d = jax.tree.map(
                lambda x_, s_, g_: (1.0 - theta) * x_
                + theta * (sw * x_ + s_ - gamma * g_) - x_, x, s, ghat)
            return d, leaf_norms(ghat)

        self._grad = jax.jit(value_and_grad)
        self._mask = jax.jit(mask)
        self._commit = jax.jit(commit)
        self._dots = jax.jit(probe_dots)
        self._flat_ghat = jax.jit(lambda g, noise: jnp.concatenate([
            (a + b).reshape(-1) for a, b in zip(jax.tree.leaves(g),
                                                jax.tree.leaves(noise))]))
        self._sparsify = jax.jit(sparsify, static_argnums=(2, 3))
        self._add = jax.jit(lambda a, b: q(jax.tree.map(jnp.add, a, b)))
        self._axpy = jax.jit(lambda s, w, v: jax.tree.map(
            lambda s_, v_: s_ + w * v_, s, v))
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda x, x0: leaf_norms(
            jax.tree.map(lambda a, b: a - b.astype(jnp.float32), x, x0)))

    def _key(self, node: int, t: int):
        return jax.random.fold_in(jax.random.fold_in(self.base_key, node), t)

    def init(self, x0) -> None:
        """All nodes start at ``x0`` (the seed's weights). A lone node has
        no neighbours, so its sum s stays 0 and is kept as scalars."""
        self.x = [self._q(jax.tree.map(lambda a: a.astype(jnp.float32),
                                       jax.device_put(x0, d)))
                  for d in self.devices]
        if self.n == 1:
            self.s = [jax.tree.map(lambda a: jnp.zeros((), a.dtype), self.x[0])]
        else:
            self.s = [jax.tree.map(lambda a: (1.0 - self.w[i, i]) * a, x)
                      for i, x in enumerate(self.x)]
        self.d = [jax.tree.map(jnp.zeros_like, x) for x in self.x]
        self.t = 0

    def advance(self) -> None:
        """Phase 1: every node sends S(d) and advances the public copies."""
        bkey = jax.random.fold_in(self.base_key, 0)   # the one wire plane
        sd = [self._sparsify(self.d[j],
                             jax.random.fold_in(jax.random.fold_in(bkey, j),
                                                self.t),
                             self.p, self.block) for j in range(self.n)]
        self.x = [self._add(x, s) for x, s in zip(self.x, sd)]
        if self.fault == "no_exchange":
            return
        for i in range(self.n):
            for j in range(self.n):
                if i != j and self.w[i, j]:
                    self.s[i] = self._axpy(
                        self.s[i], float(self.w[i, j]),
                        jax.device_put(sd[j], self.devices[i]))

    def step(self, tokens, labels, probes=(), keep_ghat=False) -> dict:
        """advance, gradient at the advanced x, commit. ``tokens`` and
        ``labels`` are (nodes, batch, seq). Returns the mean ``loss`` and,
        per node and leaf, the norms of the raw gradient (``raw``), of the
        clipped one (``clipped``) and of the noised one (``ghat``).

        ``probes`` are (nodes, coordinates) arrays laid out as the
        parameters end to end (leaves in flatten order): for each, per
        node and leaf, ``dots`` holds the inner product of (probe - this
        step's noise) with this step's clipped gradient. ``keep_ghat``
        adds the noised gradient itself, laid out so, as (nodes,
        coordinates) float32 on the host (``ghat_flat``)."""
        self.advance()
        losses, raw, clipped, ghat = [], [], [], []
        dots = [[] for _ in probes]
        flat = []
        for i, dev in enumerate(self.devices):
            loss, g = self._grad(self.x[i], jax.device_put(tokens[i], dev),
                                 jax.device_put(labels[i], dev))
            key = jax.random.fold_in(self._key(i, self.t), NOISE_SALT)
            raw.append(self._norms(g))
            g, noise = self._mask(g, key)
            clipped.append(self._norms(g))
            for k, probe in enumerate(probes):
                dots[k].append(self._dots(jax.device_put(probe[i], dev),
                                          g, noise))
            if keep_ghat:
                flat.append(np.asarray(self._flat_ghat(g, noise)))
            self.d[i], gn = self._commit(self.x[i], self.s[i], g, noise,
                                         float(self.w[i, i]))
            losses.append(loss)
            ghat.append(gn)
        self.t += 1
        stack = lambda vs: np.stack([np.asarray(v) for v in vs])
        out = {"loss": float(np.mean([float(v) for v in losses])),
               "raw": stack(raw), "clipped": stack(clipped),
               "ghat": stack(ghat), "dots": [stack(d) for d in dots]}
        if keep_ghat:
            out["ghat_flat"] = np.stack(flat)
        return out

    def change_norms(self, x0) -> np.ndarray:
        """(nodes, leaves) norms of x - x0."""
        return np.stack([np.asarray(self._change(x, jax.device_put(x0, d)))
                         for x, d in zip(self.x, self.devices)])
