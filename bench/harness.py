"""What every cell shares: finding a cell's files by name, the chip check,
the compilation cache, the profiler window, the per-layer readers, and
the result line.

A cell is ``workloads/<name>.json``; it names its configuration
(``configs/<config>.json``) and its traffic mix (``traffic/<mix>.json``),
which names the driver kind (``drivers/<kind>.py``) that generates it.
A per-layer metric is ``metrics/<metric>.py``. Nothing here lists them:
a later cell or metric is a new file.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / "out" / "jax_cache"
TRACE_DIR = BENCH / "out" / "trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_workload(name: str) -> dict:
    path = BENCH / "workloads" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no cell {name!r}: {path} does not exist")
    w = json.loads(path.read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return {**mix, **w, "name": name}


def load_config(name: str) -> dict:
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c["name"] = name
    return c


def reference_module(config: dict):
    """The plain reference that sits beside the configuration."""
    return importlib.import_module(f"bench.configs.{config['reference']}")


def driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def metric_readers() -> dict:
    """{metric name: module} for every ``metrics/*.py``."""
    out = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[path.stem] = mod
    return out


def devices_for(chips: int):
    """The first ``chips`` TPU devices; raises ``NoChip`` otherwise."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX platform is {devs[0].platform!r}, not tpu")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    pathlib.Path(path).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> int:
    """The allocator's peak on the fullest chip."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Window:
    """The measured window on the host clock, traced by the profiler when
    ``trace`` is on, into ``out/trace/<tag>`` (cleared when the next
    traced window of that tag starts). ``annotate(name)`` marks what the
    host is doing, so an idle gap of the device can be put down to it."""

    def __init__(self, trace: bool, tag: str):
        self.trace = trace
        self.dir = TRACE_DIR / tag
        self.start = self.stop = None

    def __enter__(self):
        if self.trace:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.stop = time.perf_counter()
        if self.trace:
            import jax

            jax.profiler.stop_trace()
        return False

    @property
    def seconds(self) -> float:
        return self.stop - self.start

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def xplane(self) -> pathlib.Path:
        found = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return found[-1]


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, the check as printed): each number beside its limit.
    A number without a limit, or one that is not finite, fails."""
    check, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and value is not None
                and math.isfinite(value) and value <= limit)
        ok &= good
        check[name] = {"value": value, "limit": limit}
    return ok, check


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, check: dict,
                breakdown: dict | None = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return json.dumps(out)


def print_check(check: dict) -> None:
    for name, c in check.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
