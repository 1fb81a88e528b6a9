"""What every driver shares: the device check, the compile cache, host
spans on the profiler's clock, the traced window and the run record that
the metric readers are given."""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from . import xplane
from .compile_log import CompileLog
from .peaks import peaks_for

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# scratch of the benchmark inside its checkout: the compile cache (unless
# JAX_COMPILATION_CACHE_DIR names one) and the last traced window
SCRATCH = os.path.join(ROOT, ".bench_cache")


class BenchmarkError(RuntimeError):
    """The run cannot produce a result; run.py exits non-zero without a
    result line."""


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it. Raises unless it is exactly `chips`
    TPU devices of a kind the peaks table knows: no CPU fallback."""
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != chips:
        raise BenchmarkError(
            f"this cell needs {chips} TPU device(s); JAX reports {device}")
    peaks_for(device["kind"])
    return device


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path (the path is
    part of the key): where JAX_COMPILATION_CACHE_DIR says, else inside
    the checkout. Every executable is kept, however fast it compiled, so
    that a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(SCRATCH, "jax")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits (PRNGKey alone
    would drop the bits above 32 when x64 is off)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class Spans:
    """Host spans kept in memory: (name, start_s, end_s) on
    time.perf_counter, and the same span as a TraceAnnotation so that a
    traced window carries it on the profiler's clock."""

    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))


@contextlib.contextmanager
def traced_window(workload: str):
    """Profile what runs inside; yields a dict that holds the reduced
    trace under "reduced" once the block has ended. The python tracer is
    off: it slows the host loop and the reduction reads only device ops
    and TraceAnnotations."""
    out: Dict[str, Any] = {}
    trace_dir = os.path.join(SCRATCH, "trace", workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    out["path"] = xplane.newest_trace(trace_dir)
    out["reduced"] = xplane.reduce(xplane.load(out["path"]))


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.devices())


@dataclasses.dataclass
class Context:
    """What run.py hands a driver."""
    workload: dict            # the cell's entry in BENCHMARK.json
    config: dict              # benchmark/configs/<config>.json
    traffic: dict             # benchmark/traffic/<traffic>.json
    seed: int
    seconds: float
    trace: bool
    device: dict
    peaks: dict
    process_start_s: float    # time.perf_counter() when run.py started
    compile_log: CompileLog


@dataclasses.dataclass
class Record:
    """What a driver hands back; the metric readers see all of it."""
    correct: bool
    attempted: int
    failed: int
    setup_s: float
    samples: Dict[str, List[float]]      # e.g. gap_ms, ttft_ms, tick_ms
    counters: Dict[str, float]           # work counts and elapsed seconds
    spans: Spans
    trace: Optional[dict] = None         # xplane.reduce() of the traced window
    trace_counters: Optional[Dict[str, float]] = None  # work inside it
    memory_peak_bytes: int = 0
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    context: Optional[Context] = None
