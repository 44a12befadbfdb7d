"""Training cells: SDM-DSGD through the system's own distributed step.

Set-up builds the one object the window drives, the compiled step of
``repro.train.steps.make_distributed_train`` with its node-sharded
state (one node per chip, as ``repro.launch.train`` builds them), and
drives it from the seed through the check's first steps. The window
then goes on stepping that same object until ``--seconds`` have passed;
the step in flight then is finished and counted.

Once the window has closed and the system's state is freed, the plain
reference (``sdm_reference``) follows the same first steps from the
same seed, and four numbers are compared:

* ``loss_gap``: the widest gap between the system's and the reference's
  loss over the first ``check_steps`` steps (nats);
* ``grad_norm_gap``: per node and leaf, the gap between the norms of
  the first noised gradient as the update received it (read from the
  system's differential after step 1, d = -theta * gamma * g_hat),
  against the reference leaf's norm or the median leaf's, whichever is
  larger. The noise sigma * eta makes up nearly all of that norm, so
  this number checks the clip and the Gaussian mask;
* ``grad_proj_gap``: the gradient under the mask. The system's first
  g_hat, less the reference's noise (drawn from the same keys), is
  projected on the reference's clipped gradient g: per node and leaf,
  |<g_hat - sigma * eta, g> / |g| - |g||, against |g| or the median
  leaf's, whichever is larger. A gradient that is zero, scaled or
  unrelated to the reference's reads about 1; the bfloat16 rounding of
  g_hat, which swamps the rest of the difference, averages out of the
  projection;
* ``change_norm_gap``: as ``grad_norm_gap``, for the parameters' change
  over the first ``check_steps`` updates (read after the next step's
  advance has applied the last of them).

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of the last two.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from bench import flops

def model_config(config: dict):
    from repro.models.config import ModelConfig

    return ModelConfig(**config["model"])


def token_rows(seed: int, t: int, rows: int, seq: int, vocab: int):
    """Step ``t``'s (tokens, labels), (rows, seq) int32, from the seed."""
    rng = np.random.default_rng([seed, 1, t])
    toks = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def weight_key(seed: int) -> int:
    return int(np.random.default_rng([seed, 0]).integers(0, 2 ** 31 - 1))


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst (node, leaf) gap between norms, against the reference
    leaf's norm or the node's median leaf norm, whichever is larger."""
    med = np.median(ref, axis=1, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(np.maximum(ref, med), 1e-30)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    return float(np.max(gap))


def proj_gap(dots: np.ndarray, norms: np.ndarray, keep) -> float:
    """Worst (node, leaf) gap between the component of the probe's
    residual along the reference gradient (``dots / norms``) and that
    gradient's norm, against the leaf's norm or the node's median leaf
    norm, whichever is larger."""
    med = np.median(norms, axis=1, keepdims=True)
    along = dots / np.maximum(norms, 1e-30)
    gap = np.abs(along - norms) / np.maximum(np.maximum(norms, med), 1e-30)
    return float(np.max(np.where(keep, gap, 0.0)))


def compare(prog: dict, ref: dict) -> dict:
    """The four numbers, from two runs' readings (see the module doc).
    ``prog["grad_dots"]`` are the reference's ``probe_dots`` for the
    first g_hat of ``prog``."""
    keep = ref["raw_grad_norms"] >= 1e-3 * np.median(
        ref["raw_grad_norms"], axis=1, keepdims=True)
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(prog["losses"])
                                        - np.asarray(ref["losses"])))),
        "grad_norm_gap": norm_gap(prog["grad_norms"], ref["grad_norms"]),
        "grad_proj_gap": proj_gap(prog["grad_dots"], ref["clipped_norms"],
                                  keep),
        "change_norm_gap": norm_gap(prog["change_norms"],
                                    ref["change_norms"], keep),
    }


def reference_readings(w: dict, config: dict, seed: int, devices, *,
                       control: bool = False, fault=None, probes=(),
                       keep_ghat: bool = False) -> dict:
    """The plain reference's readings over the check's first steps.
    ``probes`` and ``keep_ghat`` are passed to its first step
    (``SDMReference.step``)."""
    import jax

    from bench.drivers.sdm_reference import SDMReference
    from bench.harness import reference_module

    job, m = w["job"], config["model"]
    ref_mod = reference_module(config)
    n, rows, seq = len(devices), job["rows_per_node"], job["seq_len"]
    ref = SDMReference(
        functools.partial(_ref_loss, ref_mod, m), p=job["p"],
        theta=job["theta"], gamma=job["gamma"], sigma=job["sigma"],
        clip_c=job["clip_c"], block=job["block"], devices=devices,
        base_key=jax.random.PRNGKey(job["base_key"]), control=control,
        fault=fault)
    make_x0 = jax.jit(lambda k: ref_mod.init_params(k, m, config["dtype"]))
    key = jax.random.PRNGKey(weight_key(seed))
    with jax.default_device(devices[0]):
        ref.init(make_x0(key))
    losses = []
    for t in range(w["check_steps"]):
        toks, labs = token_rows(seed, t, n * rows, seq, m["vocab_size"])
        out = ref.step(toks.reshape(n, rows, seq), labs.reshape(n, rows, seq),
                       probes=probes if t == 0 else (),
                       keep_ghat=keep_ghat and t == 0)
        losses.append(out["loss"])
        if t == 0:
            first = out
    ref.advance()
    with jax.default_device(devices[0]):
        change = ref.change_norms(make_x0(key))
    readings = {"losses": losses, "raw_grad_norms": first["raw"],
                "clipped_norms": first["clipped"],
                "grad_norms": first["ghat"], "probe_dots": first["dots"],
                "change_norms": change}
    if keep_ghat:
        readings["ghat_flat"] = first["ghat_flat"]
    del ref
    return readings


def _ref_loss(ref_mod, m, params, tokens, labels):
    return ref_mod.loss(params, m, tokens, labels)


class Program:
    """The system's compiled distributed step and its state."""

    def __init__(self, w: dict, config: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType

        from repro.core.sdm_dsgd import SDMConfig
        from repro.train import steps

        job = w["job"]
        self.w, self.config, self.seed = w, config, seed
        self.n, self.rows, self.seq = (len(devices), job["rows_per_node"],
                                       job["seq_len"])
        self.vocab = config["model"]["vocab_size"]
        # as the launcher's ``--mesh local`` builds it: one node per chip
        self.mesh = jax.make_mesh((len(devices),), ("data",),
                                  axis_types=(AxisType.Auto,),
                                  devices=devices)
        sdm = SDMConfig(p=job["p"], theta=job["theta"], gamma=job["gamma"],
                        sigma=job["sigma"], clip_c=job["clip_c"],
                        mode=job["gossip_mode"],
                        compressor=job.get("compressor"))
        self.tc = steps.DistributedTrainConfig(
            model=model_config(config), sdm=sdm, topology=job["topology"],
            method=job["method"], param_dtype=jnp.dtype(config["dtype"]))
        self.on_nodes = steps.batch_sharding(self.mesh)
        self.state = steps.init_distributed_state(
            self.tc, self.mesh, jax.random.PRNGKey(weight_key(seed)))
        step = jax.jit(steps.make_distributed_train(
            self.tc, self.mesh, jax.random.PRNGKey(job["base_key"])),
            donate_argnums=0)
        self.compiled = step.lower(self.state, *self.feed(0)).compile()
        self.t = 0
        self.built = time.perf_counter()

    def feed(self, t: int):
        import jax

        toks, labs = token_rows(self.seed, t, self.n * self.rows, self.seq,
                                self.vocab)
        return (jax.device_put(toks, self.on_nodes),
                jax.device_put(labs, self.on_nodes))

    def step(self, args) -> float:
        import jax

        self.state, loss = self.compiled(self.state, *args)
        jax.block_until_ready((self.state, loss))
        self.t += 1
        return float(loss)

    def grad_norms(self) -> np.ndarray:
        """(nodes, leaves) norms of the noised gradient the update got,
        read from the differential after the first step."""
        import jax
        import jax.numpy as jnp

        job = self.w["job"]
        sizes = [int(np.prod(a.shape[1:]))
                 for a in jax.tree.leaves(self.state.x)]
        scale = job["theta"] * job["gamma"]

        @jax.jit
        def norms(d):
            v = d[0].reshape(self.n, -1)
            offs = np.concatenate([[0], np.cumsum(sizes)])
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                v[:, a:b]), axis=1)) for a, b in zip(offs[:-1], offs[1:])],
                axis=1) / scale

        return np.asarray(norms(self.state.d))

    def first_ghat(self) -> np.ndarray:
        """(nodes, coordinates) the noised gradient the first update got,
        read from the differential after the first step, leaves end to
        end, in the parameters' type (the type the system adds it in):
        on the host, so that it outlasts the system's state."""
        import jax

        size = sum(int(np.prod(a.shape[1:]))
                   for a in jax.tree.leaves(self.state.x))
        scale = self.w["job"]["theta"] * self.w["job"]["gamma"]
        dtype = self.tc.param_dtype

        @jax.jit
        def read(d):
            return (d[0].reshape(self.n, -1)[:, :size] / -scale).astype(dtype)

        return np.asarray(read(self.state.d))

    def change_norms(self) -> np.ndarray:
        """(nodes, leaves) norms of x - x(0), x(0) made anew from the seed
        by the plain reference's initialisation."""
        import jax
        import jax.numpy as jnp

        from bench.harness import reference_module

        ref_mod = reference_module(self.config)
        m = self.config["model"]

        @jax.jit
        def norms(x, key):      # the key an argument: one program for all seeds
            x0 = ref_mod.init_params(key, m, self.config["dtype"])
            return jnp.stack(jax.tree.leaves(jax.tree.map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b[None].astype(jnp.float32)),
                    axis=tuple(range(1, a.ndim)))), x, x0)), axis=1)

        return np.asarray(norms(self.state.x,
                                jax.random.PRNGKey(weight_key(self.seed))))

    def footprint_bytes(self) -> int:
        ma = self.compiled.memory_analysis()
        return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + ma.temp_size_in_bytes + ma.generated_code_size_in_bytes
                   - ma.alias_size_in_bytes)


def program_readings(w: dict, config: dict, seed: int, devices):
    """Build the cell's one object and drive it through the check's
    first steps; returns it with its readings."""
    prog = Program(w, config, seed, devices)
    losses = []
    for t in range(w["check_steps"] + 1):
        losses.append(prog.step(prog.feed(t)))
        if t == 0:
            g_norms, ghat = prog.grad_norms(), prog.first_ghat()
    return prog, {"losses": losses[:w["check_steps"]], "grad_norms": g_norms,
                  "ghat_flat": ghat, "change_norms": prog.change_norms()}


def run(w: dict, config: dict, *, seed: int, seconds: float, window,
        devices, t_start: float) -> dict:
    from bench.harness import memory_peak_bytes

    t_build = time.perf_counter()
    prog, readings = program_readings(w, config, seed, devices)
    t_check = time.perf_counter() - prog.built
    footprint = prog.footprint_bytes()

    steps = failed = 0
    with window as win:
        setup_s = win.start - t_start
        split = {"to_build": t_build - t_start,
                 "build": prog.built - t_build, "check": t_check,
                 "rest": win.start - prog.built - t_check}
        while True:
            with win.annotate("bench.feed"):
                args = prog.feed(prog.t)
            with win.annotate("bench.step"):
                loss = prog.step(args)
            steps += 1
            failed += not np.isfinite(loss)
            if time.perf_counter() - win.start >= seconds:
                break
    tokens = steps * prog.n * prog.rows * prog.seq
    peak = memory_peak_bytes(devices)
    del prog
    ref = reference_readings(w, config, seed, devices,
                             probes=[readings.pop("ghat_flat")])
    readings["grad_dots"] = ref["probe_dots"][0]
    return {
        "kind": "train", "attempted": steps, "failed": failed,
        "setup_s": setup_s, "window_s": win.seconds,
        "end_to_end": {"train_tokens_per_s": (tokens / win.seconds,
                                              "tokens/s")},
        "numbers": compare(readings, ref),
        "memory_peak_bytes": max(peak, footprint),
        "counters": {"steps": steps, "tokens": tokens, "nodes": len(devices),
                     "chips": len(devices),
                     "flops_per_token": flops.train_flops_per_token(
                         config["model"], w["job"]["seq_len"]),
                     "pack": flops.fixedk_pack(config["model"], w["job"]),
                     "footprint_bytes": footprint,
                     "allocator_peak_bytes": peak,
                     "setup_split_s": split},
    }
