"""The pluggable Compressor layer: registry/spec parsing, the two
SDMConfig spellings of a wire format, roundtrip unbiasedness,
pad-to-max-k heterogeneous payloads, exact wire-bit accounting,
accountant wiring, and the compressed push-sum invariants."""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (compressor, gradient_push, method,
                        plane as plane_mod, privacy, sdm_dsgd, sparsifier,
                        topology)


# ---------------------------------------------------------------------------
# Registry + spec parsing.
# ---------------------------------------------------------------------------

def test_registry_and_specs():
    assert set(compressor.names()) >= {"bernoulli", "fixedk", "block",
                                       "rows", "qsgd"}
    assert isinstance(compressor.make("bernoulli", p=0.3),
                      compressor.BernoulliCompressor)
    fk = compressor.make("fixedk:4", p=0.3)
    assert isinstance(fk, compressor.FixedKCompressor) and fk.block == 4
    assert compressor.make("block:256", p=0.5).block == 256
    assert compressor.make("block", p=0.5).block == 128
    q = compressor.make("qsgd:4")
    assert isinstance(q, compressor.QSGDCompressor) and q.bits == 4
    with pytest.raises(ValueError, match="registered:"):
        compressor.make("no-such-compressor")
    with pytest.raises(ValueError):
        compressor.make("qsgd:12")     # int8 wire caps at 8 bits
    with pytest.raises(ValueError):
        compressor.make("fixedk", p=0.0)


def test_sdm_config_selects_compressor_by_name():
    cases = [("bernoulli", "bernoulli", 1),
             ("fixedk", "fixedk_packed", 1),
             ("fixedk:64", "fixedk_packed", 64),
             ("block:8", "fixedk_packed", 8),
             ("rows", "fixedk_rows", 1),
             ("qsgd:4", "qsgd", 1)]
    for spec, mode, block in cases:
        cfg = sdm_dsgd.SDMConfig(compressor=spec, p=0.25, theta=0.3)
        assert cfg.mode == mode, spec
        if mode == "fixedk_packed":
            assert cfg.pack_block == block
    assert sdm_dsgd.SDMConfig(compressor="qsgd:4", theta=0.3).qsgd_bits == 4
    with pytest.raises(ValueError, match="registered:"):
        sdm_dsgd.SDMConfig(compressor="zip")
    # compressor_of resolves either spelling to the same object type
    c1 = sdm_dsgd.compressor_of(sdm_dsgd.SDMConfig(compressor="block:8"))
    c2 = sdm_dsgd.compressor_of(
        sdm_dsgd.SDMConfig(mode="fixedk_packed", pack_block=8))
    assert c1 == c2


SPELLING_HELPER = (pathlib.Path(__file__).parent / "helpers"
                   / "spelling_check.py")


@pytest.fixture(scope="module")
def spelling_jaxprs() -> dict:
    """{spec: (legacy jaxpr hash, spec jaxpr hash)} of one 4-node step."""
    out = subprocess.run(
        [sys.executable, str(SPELLING_HELPER)], capture_output=True,
        text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(
            pathlib.Path(__file__).parent.parent / "src")))
    assert out.returncode == 0, out.stderr[-3000:]
    return {toks[1]: (toks[2], toks[3]) for toks in
            (ln.split() for ln in out.stdout.splitlines())
            if toks and toks[0] == "SPELLING"}


@pytest.mark.parametrize("spec,legacy", [
    pytest.param(spec, legacy, id=spec) for spec, legacy in [
        ("bernoulli", {"mode": "bernoulli"}),
        ("fixedk", {"mode": "fixedk_packed"}),
        ("block:128", {"mode": "fixedk_packed", "pack_block": 128}),
        ("rows", {"mode": "fixedk_rows"}),
        ("qsgd:8", {"mode": "qsgd"})]])
def test_mode_and_compressor_spellings_resolve_alike(spec, legacy,
                                                     spelling_jaxprs):
    """A config resolves its Compressor once, from either spelling, to
    the same object; == and hash still read only the declared fields;
    and a 4-node step traces to the same jaxpr under both spellings."""
    kw = dict(p=0.25, theta=0.5, gamma=0.01, sigma=0.5, clip_c=1.0)
    old = sdm_dsgd.SDMConfig(**legacy, **kw)
    new = sdm_dsgd.SDMConfig(compressor=spec, **kw)
    assert sdm_dsgd.compressor_of(old) == sdm_dsgd.compressor_of(new) \
        == compressor.make(spec, p=0.25)
    assert sdm_dsgd.compressor_of(new) is new.wire   # built once
    for cfg in (old, new):
        # the hash of the fields a caller passes, as before the config
        # kept its resolved Compressor
        fields = tuple(getattr(cfg, f.name)
                       for f in dataclasses.fields(cfg) if f.init)
        assert hash(cfg) == hash(fields)
        assert cfg == dataclasses.replace(cfg)
    # the spec spelling, its spec dropped, is the legacy config
    bare = dataclasses.replace(new, compressor=None)
    assert bare == old and hash(bare) == hash(old)
    legacy_jaxpr, spec_jaxpr = spelling_jaxprs[spec]
    assert legacy_jaxpr == spec_jaxpr


# ---------------------------------------------------------------------------
# Roundtrip semantics.
# ---------------------------------------------------------------------------

def _x(shape=(13, 7), seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


@pytest.mark.parametrize("spec", ["bernoulli", "fixedk", "fixedk:4",
                                  "rows", "qsgd:8"])
def test_roundtrip_unbiased(spec):
    x = _x()
    comp = compressor.make(spec, p=0.4)
    keys = jax.random.split(jax.random.PRNGKey(1), 3000)
    mean = jnp.mean(jax.vmap(
        lambda k: comp.decompress(comp.compress(k, x)))(keys), axis=0)
    tol = 0.01 if spec.startswith("qsgd") else 0.12
    assert float(jnp.max(jnp.abs(mean - x))) < tol
    # payload is shape-static: same shapes for any key
    p1 = comp.compress(keys[0], x)
    p2 = comp.compress(keys[1], x)
    assert jax.tree.map(jnp.shape, p1) == jax.tree.map(jnp.shape, p2)


def test_fixedk_exact_count_and_scale():
    x = _x((91,))
    comp = compressor.make("fixedk", p=0.3)
    pl = comp.compress(jax.random.PRNGKey(0), x)
    k = sparsifier.num_kept(91, 0.3)
    assert pl.values.shape == (k, 1) and pl.indices.shape == (k,)
    dense = comp.decompress(pl)
    assert int(jnp.sum(dense != 0)) == k
    np.testing.assert_allclose(
        np.asarray(dense)[np.asarray(pl.indices)].ravel(),
        np.asarray(pl.values).ravel(), rtol=1e-6)


def test_qsgd_levels_bounded_int8():
    x = _x((257,), seed=3) * 100.0
    # b=8: unpacked int8 wire, levels within +-s
    comp8 = compressor.make("qsgd:8")
    pl8 = comp8.compress(jax.random.PRNGKey(2), x)
    assert pl8.values.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(pl8.values.astype(jnp.int32)))) <= \
        2 ** (8 - 1) - 1
    # b=4: SUB-BYTE u8-packed wire (2 levels per byte); decompressed
    # levels still within +-s of the scale
    comp = compressor.make("qsgd:4")
    pl = comp.compress(jax.random.PRNGKey(2), x)
    s = 2 ** (4 - 1) - 1
    assert pl.values.dtype == jnp.uint8
    assert pl.values.shape == (-(-257 // 2),)   # ceil(d/2) bytes
    levels = comp.decompress(pl) * s / pl.scale
    assert float(jnp.max(jnp.abs(levels))) <= s + 1e-4
    # zero input compresses to an exactly-zero payload (consensus is a
    # fixed point of the compressed dynamics)
    z = comp.compress(jax.random.PRNGKey(2), jnp.zeros((5,)))
    assert float(jnp.max(jnp.abs(comp.decompress(z)))) == 0.0


# ---------------------------------------------------------------------------
# Heterogeneous per-node p: pad-to-max-k payloads.
# ---------------------------------------------------------------------------

def test_hetp_pad_to_max_k():
    p = (0.1, 0.3, 0.5)
    comp = compressor.make("fixedk", p=p)
    x = _x((91,))
    kmax = sparsifier.num_kept(91, 0.5)
    for node in range(3):
        pl = comp.compress(jax.random.PRNGKey(0), x, node=node)
        # ONE static wire shape for every node...
        assert pl.values.shape == (kmax, 1)
        # ...but each node's informative payload is its own k_i
        k_i = sparsifier.num_kept(91, p[node])
        dense = comp.decompress(pl)
        assert int(jnp.sum(dense != 0)) == k_i
        assert comp.wire_elements((91,), node=node) == k_i
    with pytest.raises(ValueError, match="node"):
        comp.compress(jax.random.PRNGKey(0), x)
    # accounting with no node named charges the worst-case (max-p) node
    assert comp.wire_elements((91,)) == kmax


def test_hetp_fixedk_reference_runs_and_accounts():
    topo = topology.ring(4)
    cfg = sdm_dsgd.SDMConfig(p=(0.2, 0.3, 0.4, 0.5), theta=0.2, gamma=0.2,
                             mode="fixedk_packed")
    sim = method.get("sdm-dsgd").make_reference(topo, cfg)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(4, 16, 8)) / 3.0, jnp.float32)
    x_true = rng.normal(size=(8,))
    b = jnp.asarray(np.asarray(a) @ x_true
                    + 0.01 * rng.normal(size=(4, 16)), jnp.float32)

    def grad_fn(params, batch):
        del batch
        g = jax.vmap(lambda w, aa, bb: aa.T @ (aa @ w - bb) / 16.0)(
            params["w"], a, b)
        loss = jnp.mean((jnp.einsum("nbd,nd->nb", a, params["w"]) - b) ** 2)
        return {"w": g}, loss

    state = sim.init({"w": jnp.zeros((4, 8))})
    key = jax.random.PRNGKey(0)
    step = jax.jit(lambda s, k: sim.step(s, grad_fn, None, k))
    losses = []
    for _ in range(200):
        key, sub = jax.random.split(key)
        state, loss = step(state, sub)
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0]
    # per-node accounting matches each node's own k OVER THE WIRE PLANE
    # (the padded (rows, LANE) buffer the transport actually draws on);
    # the RDP accountant still charges the worst-case node
    params = {"w": jnp.zeros((8,))}
    plane_d = plane_mod.ParamPlane.for_tree(params).padded_size
    per_node = [sdm_dsgd.transmitted_elements_per_step(params, cfg, i)
                for i in range(4)]
    assert per_node == [sparsifier.num_kept(plane_d, pi) for pi in cfg.p]
    pp = privacy.PrivacyParams.from_compressor(
        sdm_dsgd.compressor_of(cfg), G=1.0, m=100, tau=0.1, sigma=1.0)
    assert pp.p_worst == 0.5


# ---------------------------------------------------------------------------
# Wire accounting in bits.
# ---------------------------------------------------------------------------

def test_wire_bits_accounting():
    d = 1024
    shape = (d,)
    fk = compressor.make("fixedk", p=0.25)
    k = sparsifier.num_kept(d, 0.25)
    assert fk.wire_bits(shape, index_sync=True) == k * 32
    assert fk.wire_bits(shape) == k * 32 + k * 10   # ceil(log2 1024) = 10
    q = compressor.make("qsgd:4")
    assert q.wire_bits(shape) == d * 4 + 32          # + the norm scalar
    bern = compressor.make("bernoulli", p=0.25)
    assert bern.wire_bits(shape, index_sync=True) == 256 * 32
    # the companion metric threads through the config layer
    params = {"w": jnp.zeros((d,))}
    cfg = sdm_dsgd.SDMConfig(compressor="fixedk", p=0.25)
    assert sdm_dsgd.transmitted_bits_per_step(params, cfg) == k * 32
    assert sdm_dsgd.transmitted_bits_per_step(
        params, cfg, index_sync=False) == k * 32 + k * 10
    cfg_q = sdm_dsgd.SDMConfig(compressor="qsgd:4")
    assert sdm_dsgd.transmitted_bits_per_step(params, cfg_q) == d * 4 + 32
    # method-level: dense baselines fall back to elements * 32
    meth = method.get("dsgd")
    from repro.core import baselines
    assert method.transmitted_bits(meth, params,
                                   baselines.DSGDConfig()) == d * 32


def test_privacy_params_from_compressor():
    base = dict(G=2.0, m=50, tau=0.1, sigma=1.2)
    pp = privacy.PrivacyParams.from_compressor(
        compressor.make("fixedk", p=0.3), **base)
    assert pp.p == 0.3
    het = privacy.PrivacyParams.from_compressor(
        compressor.make("fixedk", p=(0.1, 0.4)), **base)
    assert het.p_worst == 0.4
    q = privacy.PrivacyParams.from_compressor(compressor.make("qsgd"), **base)
    assert q.p == 1.0    # quantizers release every coordinate


# ---------------------------------------------------------------------------
# Compressed push-sum: conservation + consensus within tolerance.
# ---------------------------------------------------------------------------

def _pure_gossip(cfg, topo, stack, steps):
    sim = method.get("gradient-push").make_reference(topo, cfg)
    state = sim.init(stack)
    zero_grad = lambda p, b: (jax.tree.map(jnp.zeros_like, p), 0.0)
    key = jax.random.PRNGKey(0)
    step = jax.jit(lambda s, k: sim.step(s, zero_grad, None, k))
    for _ in range(steps):
        key, sub = jax.random.split(key)
        state, _ = step(state, sub)
    return sim, state


def test_compressed_push_sum_consensus():
    """Error-compensated compressed push-sum on a directed graph:
    sum x / sum w stays EXACTLY mass-conserved under compression, and the
    per-node de-biased estimates land within tolerance of the
    uncompressed push-sum limit."""
    topo = topology.directed_erdos_renyi(6, 0.3, seed=2)
    rng = np.random.default_rng(0)
    stack = {"w": jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)}
    mean0 = np.mean(np.asarray(stack["w"]), axis=0)

    sim_u, st_u = _pure_gossip(
        gradient_push.GradientPushConfig(gamma=0.0), topo, stack, 80)
    z_u = np.asarray(sim_u.eval_params(st_u)["w"])
    assert np.max(np.abs(z_u - mean0)) < 1e-5      # uncompressed limit

    cfg_c = gradient_push.GradientPushConfig(
        gamma=0.0, compressor="fixedk", p=0.4)   # default CHOCO chi
    sim_c, st_c = _pure_gossip(cfg_c, topo, stack, 80)
    # mass conservation survives compression bit-exactly
    cons = np.asarray(sim_c.consensus(st_c)["w"])
    np.testing.assert_allclose(cons, mean0, atol=1e-4)
    # de-biased estimates within tolerance of the uncompressed consensus
    z_c = np.asarray(sim_c.eval_params(st_c)["w"])
    assert np.max(np.abs(z_c - mean0)) < 0.05
    # compressed state carries the public-copy machinery
    assert st_c.xhat is not None and st_c.s is not None
    assert st_u.xhat is None and st_u.s is None


def test_compressed_push_state_fields():
    meth = method.get("gradient-push")
    plain = gradient_push.GradientPushConfig()
    comp = gradient_push.GradientPushConfig(compressor="fixedk", p=0.2)
    assert method.state_fields_of(meth, plain) == meth.state_fields
    extra = method.state_fields_of(meth, comp)
    assert ("xhat", method.PLANE) in extra and ("s", method.PLANE) in extra
    x = {"w": jax.ShapeDtypeStruct((4, 7), jnp.float32)}
    # public copy + neighbour sum are WIRE PLANES: (n, rows, LANE) f32
    sds = method.state_shape_dtype(meth, x, comp)
    assert sds.xhat[0].shape == (4, 1, plane_mod.LANE)
    assert sds.s[0].shape == (4, 1, plane_mod.LANE)
    sds_plain = method.state_shape_dtype(meth, x, plain)
    assert sds_plain.xhat is None and sds_plain.s is None
    # wire accounting: compressed push transmits the p-fraction OF THE
    # PLANE + mass
    params = {"w": jnp.zeros((100,))}
    plane_d = plane_mod.ParamPlane.for_tree(params).padded_size   # 128
    assert meth.transmitted_elements(params, plain) == plane_d + 1
    assert meth.transmitted_elements(params, comp) == \
        sparsifier.num_kept(plane_d, 0.2) + 1
    bits = method.transmitted_bits(meth, params, comp)
    k = sparsifier.num_kept(plane_d, 0.2)
    assert bits == k * 32 + k * 7 + 32   # values + explicit idx + mass


@pytest.mark.parametrize("rounds", [2, 3])
def test_compressed_push_on_time_varying_schedules(rounds):
    """Replica-correct compressed push-sum runs on B-connected
    time-varying sequences (the pre-replica code had to REJECT them):
    sum x / sum w stays mass-conserved to float tolerance on every P(t),
    and with sigma=0 the per-node de-biased estimates converge to the
    exact initial mean."""
    from repro.core import gossip
    seq = gossip.sequence_by_name(f"matchings:{rounds}", 6, seed=0)
    cfg = gradient_push.GradientPushConfig(
        gamma=0.0, sigma=0.0, compressor="fixedk", p=0.4)
    sim = method.get("gradient-push").make_reference(seq, cfg)
    assert sim.replica_exact    # genuinely time-varying -> replica path
    rng = np.random.default_rng(1)
    stack = {"w": jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)}
    mean0 = np.mean(np.asarray(stack["w"]), axis=0)
    state = sim.init(stack)
    zero_grad = lambda p, b: (jax.tree.map(jnp.zeros_like, p), 0.0)
    key = jax.random.PRNGKey(0)
    step = jax.jit(lambda s, k: sim.step(s, zero_grad, None, k))
    for t in range(240):
        key, sub = jax.random.split(key)
        state, _ = step(state, sub)
        if t % 60 == 0:   # conservation holds at EVERY step, not just the end
            cons = np.asarray(sim.consensus(state)["w"])
            np.testing.assert_allclose(cons, mean0, atol=1e-4)
    cons = np.asarray(sim.consensus(state)["w"])
    np.testing.assert_allclose(cons, mean0, atol=1e-4)
    # per-node de-biased estimates reach the exact mean (consensus)
    z = np.asarray(sim.eval_params(state)["w"])
    assert np.max(np.abs(z - mean0)) < 5e-3
    # uncompressed push-sum stays exact on time-varying sequences too
    method.get("gradient-push").make_reference(
        seq, gradient_push.GradientPushConfig())


def test_error_feedback_rejected_with_qsgd():
    """EF's p-scaling undoes the sparsifiers' 1/p amplification; the
    quantizer has none, so the combination would discard (1-p) of every
    update — reject it."""
    with pytest.raises(ValueError, match="sparsifier"):
        sdm_dsgd.SDMConfig(compressor="qsgd", error_feedback=True)


def test_new_family_rides_generic_payload_transport():
    """README's 'Adding a compressor' contract: a freshly registered
    family reaches SDM-DSGD with NO sdm_dsgd-side mapping — it resolves
    to mode='payload' and runs through gossip.exchange_payload."""
    import dataclasses as dc

    @jax.tree_util.register_static
    @dc.dataclass(frozen=True)
    class SignCompressor(compressor.Compressor):
        """1-bit sign + per-leaf l1/d magnitude (signSGD-style)."""
        name: str = dc.field(default="sign", init=False, repr=False)

        def compress(self, key, x, *, node=None):
            mag = jnp.mean(jnp.abs(x.astype(jnp.float32)))
            return compressor.Payload(
                values=jnp.sign(x).astype(jnp.int8), scale=mag,
                shape=tuple(x.shape), meta=("sign",))

        def decompress(self, pl):
            return pl.scale * pl.values.astype(jnp.float32)

        def wire_elements(self, shape, node=None):
            return int(np.prod(shape))

        def wire_bits(self, shape, *, value_bits=32, index_sync=False,
                      node=None):
            return int(np.prod(shape)) + 32

    compressor.register("sign", lambda p, arg=None: SignCompressor(p=p))
    try:
        cfg = sdm_dsgd.SDMConfig(compressor="sign", p=0.5, theta=0.4,
                                 gamma=0.1)
        assert cfg.mode == "payload"
        assert isinstance(sdm_dsgd.compressor_of(cfg), SignCompressor)
        params = {"w": jnp.zeros((64,))}
        # plane convention: the payload is the padded (1, LANE) plane
        assert sdm_dsgd.transmitted_bits_per_step(params, cfg) == \
            plane_mod.LANE + 32
        # a short reference run actually exercises the payload roundtrip
        sim = method.get("sdm-dsgd").make_reference(topology.ring(4), cfg)
        state = sim.init({"w": jnp.zeros((4, 8))})
        zero_grad = lambda p_, b: (jax.tree.map(jnp.zeros_like, p_), 0.0)
        for _ in range(3):
            state, _ = sim.step(state, zero_grad, None, jax.random.PRNGKey(0))
        assert all(bool(jnp.all(jnp.isfinite(x)))
                   for x in jax.tree.leaves(state.x))
    finally:
        compressor._FAMILIES.pop("sign", None)


def test_sdm_coercion_carries_compressor_to_push():
    sdm = sdm_dsgd.SDMConfig(compressor="fixedk:2", p=0.3, theta=0.4,
                             gamma=0.05, sigma=0.0)
    gp = method.get("gradient-push").coerce_config(sdm)
    assert gp.compressor == "fixedk:2" and gp.p == 0.3
    # legacy mode-only configs still coerce to uncompressed push-sum
    gp2 = method.get("gradient-push").coerce_config(
        sdm_dsgd.SDMConfig(mode="fixedk_packed", p=0.3, theta=0.4))
    assert gp2.compressor is None
