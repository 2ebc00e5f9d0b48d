"""What every part of the benchmark shares: finding a cell's files by name,
seeds, the compile counter, host spans, device facts and quantiles.

Nothing here imports the program under test.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import statistics
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """A cell, file or device the benchmark cannot run with."""


# -- the cell's files ---------------------------------------------------------

def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: Optional[str] = None):
    """Import a Python file by path (names may hold '.' and '-')."""
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        name or "bench_file_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with everything it names, found by name:
    its configuration's file, its traffic file, its driver and reference,
    and the readers of its per-layer metrics."""

    def __init__(self, root: pathlib.Path, name: str):
        self.root = root
        self.bench = root / "bench"
        spec = load_json(root / "BENCHMARK.json")
        by_name = {w["name"]: w for w in spec["workloads"]}
        if name not in by_name:
            raise BenchError(f"unknown workload {name!r}; known: "
                             f"{sorted(by_name)}")
        self.spec = spec
        self.entry = by_name[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(root / self.config_entry["file"])
        self.traffic = load_json(self.bench / "traffic"
                                 / f"{self.entry['traffic']}.json")
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]

    def driver(self):
        return load_module(self.bench / "drivers" / f"{self.config['driver']}.py")

    def reference(self):
        return load_module(self.bench / "reference"
                           / f"{self.config['reference']}.py")

    def metric_reader(self, metric_name: str):
        return load_module(self.bench / "metrics" / f"{metric_name}.py")


# -- seeds --------------------------------------------------------------------

def sub_seeds(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit seeds from one ``--seed`` of any size (the
    program's RNGs take 32-bit seeds; ``--seed`` may be larger)."""
    if seed < 0:
        raise BenchError(f"--seed must be >= 0, got {seed}")
    state = np.random.SeedSequence(seed).generate_state(n)
    return [int(s) & 0x7FFFFFFF for s in state]


# -- counters and spans -------------------------------------------------------

class CompileClock:
    """Counts JAX's executable builds. ``backend_compile_duration`` is
    recorded around every build, a persistent-cache hit included; a hit
    also records ``cache_hits``. So compiles = builds - hits."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.builds = 0
        self.hits = 0
        self.seconds = 0.0

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self.BUILD:
            self.builds += 1
            self.seconds += duration

    def on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1

    def register(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)

    def snapshot(self) -> Dict[str, float]:
        return {"builds": self.builds, "hits": self.hits,
                "compiles": self.builds - self.hits, "seconds": self.seconds}


class Spans:
    """Host-clock spans the benchmark records around its calls into each
    layer. With ``annotate`` they also go into the profiler's trace as
    ``TraceAnnotation``s, so idle gaps on the device can be named by what
    the host was doing."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.spans: Dict[str, List[float]] = {}
        self.recording = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(f"bench.{name}")
        else:
            ctx = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ctx:
            yield
        if self.recording:
            self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def mean_ms(self, name: str) -> Optional[float]:
        vals = self.spans.get(name)
        return 1e3 * float(np.mean(vals)) if vals else None


# -- devices ------------------------------------------------------------------

def accelerator(chips: int, dry: bool):
    """The devices the cell runs on. Without ``dry`` a JAX that finds no
    TPU, or fewer chips than the cell asks for, is an error: the benchmark
    never measures on the CPU."""
    import jax

    devices = jax.devices()
    if not dry:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX's first device is "
                             f"{devices[0].platform} ({devices[0]})")
        if len(devices) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX sees "
                             f"{len(devices)}")
    return devices[:chips]


def peak_bytes(devices: Sequence[Any]) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices`` (None where the
    backend keeps no statistics, as the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def device_record(devices: Sequence[Any], peak: Optional[int]) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# -- statistics ---------------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), None when empty."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median, as the bounds are set."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
