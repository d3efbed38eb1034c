"""What every cell shares: the chip check, the compile clock, host spans,
the window's statistics, the per-layer metric readers, the profiler window
and the result line."""
from __future__ import annotations

import contextlib
import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def require_chips(n: int) -> List:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


class CompileClock:
    """Seconds JAX spends lowering and compiling programs (persistent-cache
    loads included), with the cache hits and misses, since construction."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.cache_hits, self.cache_misses


class GcClock:
    """Seconds the host spends in Python's garbage collector, and the
    collections it makes, from construction until ``close``."""

    def __init__(self):
        self.seconds = 0.0
        self.collections = 0
        self._t0 = None
        gc.callbacks.append(self._phase)

    def _phase(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.collections += 1
            self._t0 = None

    def close(self) -> tuple:
        gc.callbacks.remove(self._phase)
        return self.seconds, self.collections


class Spans:
    """Host spans around the benchmark's calls into the program's layers:
    a ``TraceAnnotation`` (so the profiler's trace carries them) and the
    host seconds spent in each, by name."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self.seconds[name] = self.seconds.get(name, 0.0) + dt

    def reset(self) -> None:
        self.seconds.clear()


def p95(values) -> float:
    """Nearest-rank 95th percentile; a missing value (``inf``) counts as
    the worst."""
    vals = sorted(values)
    if not vals:
        return math.inf
    return vals[max(0, math.ceil(0.95 * len(vals)) - 1)]


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def load_metric(name: str):
    """The reader module ``bench/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(names, ctx) -> Dict[str, dict]:
    """Each named per-layer metric its reader finds something to read in
    ``ctx`` for; the others are left out."""
    out = {}
    for name in names:
        mod = load_metric(name)
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": mod.UNIT}
    return out


class ProfilerWindow:
    """The JAX profiler around a measured window, writing under the
    checkout and removing its files once reduced."""

    def __init__(self, enabled: bool, workload: str):
        self.enabled = enabled
        self.dir = os.path.join(ROOT, ".bench_trace", workload)
        self.started = False

    def start(self) -> None:
        if self.enabled:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host: TraceMe spans only
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started = True

    def stop(self) -> Optional[str]:
        """Stop tracing; the path of the ``.xplane.pb`` written."""
        if not self.started:
            return None
        jax.profiler.stop_trace()
        self.started = False
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def emit(result: dict, checks: Dict[str, tuple]) -> None:
    """Print the compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard output,
    with the same numbers under ``checks``, its last key."""
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in checks.items()}
    print(json.dumps(result), flush=True)
