"""The profiler trace of a ``--trace 1`` run, and its reduction to numbers.

The run reads the first ``TRACE_SECONDS`` of its window from the trace.
The benchmark marks that span with a ``bench.window`` annotation and each call into a
layer with ``bench.<name>``; both land on the host plane, on the same
clock as the device's operations.

``reduce`` reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``:

* busy time of a chip is the union of its ``XLA Ops`` intervals inside the
  marked window; idle share is one minus busy over the window;
* each program's device time is the sum of its ``XLA Modules`` events, by
  module name (a jitted function ``f`` is the module ``jit_f``);
* each idle gap on a chip is named by the ``bench.*`` host span that
  overlaps it most (``host.none`` where the host was in none).

A trace without a TPU plane, or without the ``bench.window`` mark, is an
error, never a zero.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Tuple

import numpy as np

TRACE_SECONDS = 5.0
WINDOW_MARK = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class TraceError(RuntimeError):
    """The trace lacks what the reduction needs."""


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]                      # per chip
    modules: Dict[str, Tuple[int, float]]         # name -> (events, seconds), all chips
    device_ops: List[Tuple[str, float]]           # top ops, seconds, mean over chips
    idle_gaps: List[Tuple[str, float]]            # host span -> idle seconds, mean over chips

    @property
    def busy_mean_s(self) -> float:
        return float(np.mean(list(self.busy_s.values())))

    def idle_share(self) -> float:
        return 1.0 - self.busy_mean_s / self.window_s

    def module_events(self, fragment: str) -> Tuple[int, float]:
        """(events, device seconds) of every module whose name holds
        ``fragment``; raises when there is none."""
        n, s = 0, 0.0
        for name, (k, secs) in self.modules.items():
            if fragment in name:
                n, s = n + k, s + secs
        if n == 0:
            raise TraceError(f"no program named like {fragment!r} in the "
                             f"trace; modules: {sorted(self.modules)[:20]}")
        return n, s


class Tracer:
    """Starts the profiler when the window opens and closes the
    ``bench.window`` mark ``seconds`` later. The profiler itself stops
    only when the window closes: stopping it writes the trace, which takes
    seconds, and inside the window that would stall the host mid-run."""

    def __init__(self, directory: str, seconds: float = TRACE_SECONDS):
        self.directory = directory
        self.seconds = seconds
        self.active = False
        self._mark = None
        self.t0 = 0.0

    def start(self) -> None:
        import jax

        # Python's own calls are not traced: that tracer slows the host
        # several times over and fills the trace. Annotations still land.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(WINDOW_MARK)
        self._mark.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def tick(self) -> None:
        if self._mark is not None and time.perf_counter() - self.t0 >= self.seconds:
            self._close_mark()

    def _close_mark(self) -> None:
        self._mark.__exit__(None, None, None)
        self._mark = None

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        if self._mark is not None:
            self._close_mark()
        jax.profiler.stop_trace()
        self.active = False

    def path(self) -> str:
        files = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise TraceError(f"no .xplane.pb under {self.directory}")
        return max(files, key=os.path.getmtime)


_OP = re.compile(r"^(%?[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def op_label(text: str) -> str:
    """``%fusion.70 (fusion)`` from an op's HLO text in the trace."""
    m = _OP.match(text)
    return f"{m.group(1)} ({m.group(2)})" if m else text.split(" = ")[0][:80]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a0, a1, spans) -> Dict[str, float]:
    got: Dict[str, float] = {}
    for name, s, e in spans:
        ov = min(a1, e) - max(a0, s)
        if ov > 0:
            got[name] = got.get(name, 0.0) + ov
    return got


def reduce(path: str, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host_spans: List[Tuple[str, float, float]] = []
    devices = {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == WINDOW_MARK:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name.startswith("bench."):
                    host_spans.append((name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
    if window is None:
        raise TraceError(f"no {WINDOW_MARK!r} span in {path}")
    if not devices:
        raise TraceError(f"no TPU device plane in {path}")
    lo, hi = window
    busy: Dict[int, float] = {}
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for dev, plane in devices.items():
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            raise TraceError(f"device {dev} has no {OPS_LINE!r} line: "
                             f"{sorted(lines)}")
        intervals = []
        for ev in lines[OPS_LINE].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e > lo and s < hi:
                intervals.append((s, e))
                label = op_label(ev.name)
                ops[label] = ops.get(label, 0.0) + (min(e, hi) - max(s, lo))
        merged = _union(_clip(intervals, lo, hi))
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                got = _overlap(prev, s, host_spans)
                name = max(got, key=got.get) if got else "host.none"
                gaps[name] = gaps.get(name, 0.0) + (s - prev) * 1e-9
            prev = max(prev, e)
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > lo and s < hi:
                    modules.setdefault(ev.name, []).append(ev.duration_ns * 1e-9)
    n = len(devices)
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy,
        modules={k: (len(v), sum(v)) for k, v in modules.items()},
        device_ops=sorted(((k, v * 1e-9 / n) for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v / n) for k, v in gaps.items()),
                         key=lambda kv: -kv[1])[:top],
    )
