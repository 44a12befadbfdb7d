#!/usr/bin/env python3
"""Chip smoke: drive SDM-DSGD training and paged serving once on a TPU at
the published widths of phi3-medium-14b, and check what comes out.

  python chip_smoke.py             # one chip: phases train, kernels, serve
  python chip_smoke.py --chips 4   # four chips: the decentralized path only

The model is phi3-medium-14b cut to one chip's share: one layer (one
whole period of its one-layer pattern) and an eighth of the vocabulary
(tied embedding, as published); every width is as published and the
parameters are bf16 from a seed.

* ``train`` runs ``repro.launch.train`` itself: SDM-DSGD with fixed-k
  packed payloads over 128-coordinate blocks (one lane-dense plane
  row), p=0.2, Gaussian masking with a clip, seq 2048, one sequence per
  node; one warm-up step and three timed steps. The compiled step's own
  byte count (arguments, outputs, temporaries, code, less donated
  aliases) must fit in HBM.
* ``kernels`` runs each main-path Pallas kernel at the run's real plane
  and KV-cache shapes against its jnp reference on the chip.
* ``serve`` serves 8 ragged requests through ``ServingEngine`` with the
  paged flash-decode kernel.
* ``--chips 4`` runs ``train`` on a 4-node ring (one node per chip),
  checks that every node's state lives on its own chip and that the step
  carries the expected collective-permutes, and compares the sharded
  executor with the stacked reference executor.

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a
TPU, or when any phase fails, the script exits non-zero and prints no
such line. One process holds the chips; it starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "phi3-medium-14b"
# fixed-k packed payloads over one plane row (LANE coordinates) per
# block, the benchmark's ring4 wire format (bench/workloads)
TRAIN_FLAGS = ("--arch", ARCH, "--method", "sdm-dsgd",
               "--gossip-mode", "fixedk_packed", "--compressor", "block:128",
               "--p", "0.2", "--sigma", "0.5", "--clip-c", "1.0",
               "--topology", "ring")


# rows per block of the qsgd pack reference (see phase_kernels)
REF_ROWS = 65536


class SmokeFailure(RuntimeError):
    """A phase ran but what came out is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def cut_config():
    """(published config, the one-chip cut of it)."""
    from repro import configs

    pub = configs.get_config(ARCH)
    return pub, dataclasses.replace(pub, n_layers=len(pub.period),
                                    vocab_size=pub.vocab_size // 8)


def tpu_kernel_calls(hlo_text: str):
    """The ``tpu_custom_call`` instructions (Pallas kernels) of a module."""
    return [line for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            or "@tpu_custom_call" in line]


def peak_bytes(devices):
    """[(peak_bytes_in_use, bytes_limit)] per device, where reported."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append((stats.get("peak_bytes_in_use"), stats.get("bytes_limit")))
    return out


def step_footprint(compiled) -> dict:
    """Device bytes the compiled step holds at once, per device, as the
    compiler lays them out: arguments + outputs + temporaries + code,
    less the outputs that alias (donated) arguments."""
    ma = compiled.memory_analysis()
    parts = {k: getattr(ma, f"{k}_size_in_bytes") for k in
             ("argument", "output", "temp", "generated_code", "alias")}
    parts["total"] = (parts["argument"] + parts["output"] + parts["temp"]
                      + parts["generated_code"] - parts["alias"])
    return parts


def phase_train(cfg, *, nodes: int, seq_len: int, steps: int = 4,
                extra=(), on_chip: bool = True):
    """Run the training launcher on ``cfg``; returns its ``TrainRun``."""
    import numpy as np

    from repro.launch import train as train_mod

    args = train_mod.parse_args(list(TRAIN_FLAGS) + [
        "--mesh", str(nodes), "--global-batch", str(nodes),
        "--seq-len", str(seq_len), "--steps", str(steps)] + list(extra))
    run = train_mod.train(args, cfg)
    kernels = tpu_kernel_calls(run.compiled.as_text())
    timed = run.step_s[1:]
    print(f"[train] compile_s {run.compile_s:.3f} warmup_step_s "
          f"{run.step_s[0]:.4f} timed_step_s {timed} "
          f"losses {run.losses}", flush=True)
    print(f"[train] tpu_custom_call {len(kernels)}", flush=True)
    foot = step_footprint(run.compiled)
    print(f"[train] compiled step bytes per device {foot}", flush=True)
    check(all(np.isfinite(run.losses)), f"non-finite loss {run.losses}")
    if on_chip:
        # the HBM check rests on the compiled footprint: the allocator's
        # peak is printed beside it for comparison
        for i, (peak, limit) in enumerate(peak_bytes(run.mesh.devices.flat)):
            print(f"[train] device {i} peak_bytes_in_use {peak} "
                  f"compiled_total {foot['total']} bytes_limit {limit}",
                  flush=True)
            check(limit is not None and foot["total"] < limit,
                  f"device {i}: step needs {foot['total']} bytes, "
                  f"HBM holds {limit}")
    return run


def check_placement(run) -> None:
    """Every state leaf: one addressable shard per node, node i's row on
    mesh device i and nowhere else."""
    import jax

    devices = list(run.mesh.devices.flat)
    for leaf in jax.tree.leaves(run.state):
        shards = leaf.addressable_shards
        check(len(shards) == len(devices),
              f"{leaf.shape}: {len(shards)} shards for {len(devices)} nodes")
        for sh in shards:
            start, stop, _ = sh.index[0].indices(leaf.shape[0])
            check(sh.data.shape[0] == 1 and stop - start == 1
                  and sh.device == devices[start],
                  f"{leaf.shape}: shard {sh.index} on {sh.device}")
    print(f"[placement] {len(jax.tree.leaves(run.state))} state leaves, "
          f"each one shard per node on its own device "
          f"({[d.id for d in devices]})", flush=True)


def check_permutes(run, compressor: str) -> None:
    from repro.analysis.wire_audit import expected_permutes
    from repro.launch import hlo_analysis
    from repro.train import steps as steps_mod

    got = hlo_analysis.collective_permute_count(run.compiled.as_text())
    want = expected_permutes(run.tc.method, compressor,
                             steps_mod.gossip_schedule(run.tc, run.mesh))
    print(f"[permutes] collective-permute {got} expected {want}", flush=True)
    check(got == want, f"{got} collective-permutes, expected {want}")


def phase_parity(cfg, *, nodes: int, seq_len: int, steps: int = 4) -> None:
    """The sharded executor (the launcher's own step, one node per chip)
    against the stacked reference executor on one chip, f32 at the
    highest matmul precision on both sides. Neighbours' payloads reach
    a node's x from the third step on, so ``steps`` >= 3; a large step
    size makes the movement, which is what is compared, large."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import TokenStream
    from repro.models import transformer
    from repro.train import steps as steps_mod

    # the launcher's --smoke flag selects f32 parameters
    with jax.default_matmul_precision("highest"):
        run = phase_train(cfg, nodes=nodes, seq_len=seq_len, steps=steps,
                          extra=("--smoke", "--sigma", "0", "--gamma", "0.5"),
                          on_chip=False)
        tc, mesh = run.tc, run.mesh
        meth, mcfg = tc.resolved()
        sim = meth.make_reference(steps_mod.gossip_schedule(tc, mesh), mcfg)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg,
                                         tc.param_dtype)
        state = sim.init(jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (nodes,) + p.shape), params))
        stream = TokenStream(vocab_size=cfg.vocab_size, batch=nodes,
                             seq_len=seq_len, seed=0)

        def loss(p, tokens, labels):
            logits, aux = transformer.forward(p, cfg, tokens)
            return transformer.lm_loss(logits, labels, cfg.vocab_size, aux)

        grads = jax.jit(jax.vmap(jax.grad(loss)))
        base_key = jax.random.PRNGKey(0)   # make_distributed_train's default
        for t in range(steps):
            tokens, labels = (jnp.asarray(a).reshape(nodes, 1, seq_len)
                              for a in stream.batch_at(t))
            state, _ = sim.advance(state, base_key)
            state = sim.commit(state, grads(state.x, tokens, labels),
                               base_key)
    x0 = jax.tree.leaves(jax.tree.map(np.asarray, params))
    moved = lambda x: [np.asarray(a) - b for a, b in
                       zip(jax.tree.leaves(x), x0)]
    dist, ref = moved(run.state.x), moved(state.x)
    err = max(float(np.max(np.abs(a - b))) for a, b in zip(dist, ref))
    scale = max(float(np.max(np.abs(b))) for b in ref)
    # f32 on both sides; the executors differ only in reduction order and
    # fusion, so they agree to f32 rounding of x (|x| ~ 1): far below a
    # thousandth of the movement
    tol = 1e-3 * scale
    print(f"[parity] {nodes} nodes {steps} steps max_abs_err {err:.3e} "
          f"of movement {scale:.3e} tol {tol:.3e}", flush=True)
    check(err < tol, f"sharded vs stacked reference: {err} >= {tol}")


def wire_plane_rows(cfg, dtype) -> int:
    """Rows of the (rows, LANE) wire plane of ``cfg``'s parameters."""
    import jax

    from repro.core import plane as plane_mod
    from repro.models import transformer

    shapes = jax.eval_shape(lambda k: transformer.init_params(k, cfg, dtype),
                            jax.random.PRNGKey(0))
    (rows, _), = plane_mod.ParamPlane.for_tree(shapes).plane_shapes()
    return rows


def phase_kernels(cfg, *, batch: int, max_seq: int, page_size: int,
                  dtype) -> None:
    """Each main-path kernel at the run's plane and cache shapes against
    its jnp reference: bit-exact for the wire kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import plane as plane_mod
    from repro.kernels import wire_compress
    from repro.kernels.flash_attn.decode import (paged_attention,
                                                 paged_attention_ref)

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))          # compile + warm
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a))
        return out, time.perf_counter() - t0

    rows, lane = wire_plane_rows(cfg, dtype), plane_mod.LANE
    kx, ku, kq = jax.random.split(jax.random.PRNGKey(1), 3)
    xf = jax.random.normal(kx, (rows, lane), jnp.float32)
    u = jax.random.uniform(ku, (rows, lane))
    norm = jnp.sqrt(jnp.sum(jnp.square(xf)))
    print(f"[kernels] wire plane ({rows}, {lane}) f32", flush=True)
    for bits in (2, 4, 8):
        pack = jax.jit(lambda x, u_, use, bits=bits:
                       wire_compress.qsgd_pack(x, u_, norm, bits=bits,
                                               use_kernel=use),
                       static_argnums=2)
        out_k, t_k = timed(pack, xf, u, True)
        # the reference in row blocks: its (-1, k) pack view pads k to
        # 128 lanes in TPU memory (32x at 2 bits), more than the chip
        # holds for the whole plane. Blocks of REF_ROWS rows map to
        # consecutive byte ranges, and the whole-plane norm is shared.
        per = REF_ROWS * lane // wire_compress.pack_factor(bits)
        bad, t_r = 0, 0.0
        for i, r0 in enumerate(range(0, rows, REF_ROWS)):
            out_r, dt = timed(pack, xf[r0:r0 + REF_ROWS],
                              u[r0:r0 + REF_ROWS], False)
            t_r += dt
            bad += int(jnp.sum(out_k[i * per:i * per + out_r.shape[0]]
                               != out_r))
        print(f"[kernels] qsgd_pack bits={bits} bytes {out_k.shape[0]} "
              f"mismatches {bad} kernel_s {t_k:.5f} ref_s {t_r:.5f}",
              flush=True)
        check(bad == 0 and out_k.shape[0] == i * per + out_r.shape[0],
              f"qsgd_pack bits={bits}: {bad} bytes differ from the ref")

    kvh, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    h = cfg.n_heads
    n_blocks = -(-max_seq // page_size)
    n_pages = batch * n_blocks + 1            # + the trash page 0
    k1, k2, k3 = jax.random.split(kq, 3)
    q = jax.random.normal(k1, (batch, h, dh), jnp.float32).astype(dtype)
    k_pages = jax.random.normal(k2, (n_pages, kvh, page_size, dh),
                                jnp.float32).astype(dtype)
    v_pages = jax.random.normal(k3, (n_pages, kvh, page_size, dh),
                                jnp.float32).astype(dtype)
    rng = np.random.default_rng(0)
    tables = rng.permutation(np.arange(1, n_pages)).reshape(batch, n_blocks)
    seq_lens = rng.integers(1, max_seq + 1, size=batch)
    seq_lens[0] = 0                           # an empty slot reads nothing
    tables, seq_lens = (jnp.asarray(a, jnp.int32) for a in (tables, seq_lens))
    attend = jax.jit(lambda *a: paged_attention(*a, use_kernel=True))
    out_k, t_k = timed(attend, q, k_pages, v_pages, tables, seq_lens)
    f32 = lambda a: a.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_attention_ref)(f32(q), f32(k_pages), f32(v_pages),
                                           tables, seq_lens)
    err = float(jnp.max(jnp.abs(f32(out_k) - ref)))
    # bf16 rounding (unit roundoff 2^-8) of the output, |out| <= max|v|,
    # and of the softmax weights before the PV matmul, sum(p) = 1: at
    # most 2^-7 * max|v| together; 2x margin
    tol = 2.0 ** -6 * float(jnp.max(jnp.abs(f32(v_pages))))
    print(f"[kernels] paged_attention b={batch} heads {h}/{kvh}x{dh} "
          f"page {page_size} blocks {n_blocks} {jnp.dtype(dtype).name} "
          f"max_abs_err {err:.3e} tol {tol:.3e} kernel_s {t_k:.5f}",
          flush=True)
    check(err < tol, f"paged_attention: {err} >= {tol}")


def phase_serve(cfg, *, n_requests: int, prompt_lens, new_tokens: int,
                page_size: int, dtype, on_chip: bool = True,
                seed: int = 0) -> None:
    """Serve ragged requests with continuous batching over the paged
    cache; every request must return exactly its token budget."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer
    from repro.serving import Request, ServingEngine
    from repro.serving.kv_cache import PagedKVCache

    params = transformer.init_params(jax.random.PRNGKey(seed), cfg, dtype)
    max_seq = prompt_lens[1] + new_tokens
    engine = ServingEngine(cfg, params, max_batch=n_requests,
                           max_seq=max_seq, dtype=dtype, page_size=page_size)
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(
                0, cfg.vocab_size,
                size=int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
            ).tolist(), max_new_tokens=new_tokens)
            for _ in range(n_requests)]
    t0 = time.perf_counter()
    engine.serve(reqs)
    wall = time.perf_counter() - t0
    st = engine.last_stats
    got = [len(r.output) for r in reqs]
    print(f"[serve] {n_requests} requests prompts "
          f"{[len(r.prompt) for r in reqs]} tokens {got} flash_decode "
          f"{engine.use_flash} wall_s {wall:.3f} prefills {st.prefills} "
          f"decode_steps {st.decode_steps} pages_peak {st.pages_peak}",
          flush=True)
    check(got == [new_tokens] * n_requests,
          f"token counts {got}, budget {new_tokens} each")
    if on_chip:
        check(engine.use_flash, "the engine left the flash-decode kernel off")
        kv = PagedKVCache(cfg, max_batch=n_requests, max_seq=max_seq,
                          page_size=page_size, dtype=dtype)
        zeros = jnp.zeros((n_requests,), jnp.int32)
        step = jax.jit(lambda p, tok, pages, tbl, off, we:
                       transformer.decode_step_paged(
                           p, cfg, tok, pages, {}, tbl, off, we,
                           use_flash=engine.use_flash))
        text = step.lower(params, zeros, kv.pages, kv.tables(), zeros,
                          jnp.ones((n_requests,), bool)).as_text()
        n = len(tpu_kernel_calls(text))
        print(f"[serve] decode step tpu_custom_call {n}", flush=True)
        check(n > 0, "decode step holds no paged flash-decode kernel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the decentralized path across four chips only")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache {use_compile_cache()}", flush=True)
    pub, cfg = cut_config()
    print(f"config {ARCH}: n_layers {cfg.n_layers} (published "
          f"{pub.n_layers}), vocab_size {cfg.vocab_size} (published "
          f"{pub.vocab_size}, 1/8 slice, tied embedding "
          f"{cfg.tie_embeddings}); published widths d_model {cfg.d_model} "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.resolved_head_dim} "
          f"d_ff {cfg.d_ff}; params bf16", flush=True)

    if args.chips == 1:
        phase_train(cfg, nodes=1, seq_len=2048)
        phase_kernels(cfg, batch=8, max_seq=512 + 32, page_size=16,
                      dtype=jnp.bfloat16)
        phase_serve(cfg, n_requests=8, prompt_lens=(128, 512),
                    new_tokens=32, page_size=16, dtype=jnp.bfloat16)
    else:
        run = phase_train(cfg, nodes=4, seq_len=2048)
        check_placement(run)
        check_permutes(run, "block:128")
        del run
        from repro import configs

        phase_parity(configs.get_smoke_config(ARCH), nodes=4, seq_len=64)

    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
