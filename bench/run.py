"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout. Everything it names is found by name under ``bench/``:

* ``configs/<file>``: the configuration, which names its ``driver``
  (``bench/drivers/<driver>.py``) and its ``reference``
  (``bench/reference/<reference>.py``);
* ``traffic/<traffic>.json``: the mix, read by ``bench/traffic.py``;
* ``metrics/<metric>.py``: one reader per per-layer metric.

A run fails (exit code 2, no result) when JAX finds no TPU or fewer chips
than the cell asks for. Set-up (weights, server, warm-up) counts as
``setup_s``; the window then lasts ``--seconds``. With ``--trace 1`` the
profiler records the first seconds of the window, and the result holds
the cell's per-layer metrics instead of its end-to-end ones. Checks of
correctness are printed last: on standard error, and under ``checks`` at
the end of the result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from bench import common, peaks, trace  # noqa: E402

DRY_NOTE = "CPU dry run: not a measurement"


class Run:
    """One run of a cell: what the driver needs, and the clocks the
    harness keeps around it."""

    def __init__(self, cell, seed: int, seconds: float, traced: bool,
                 dry: bool, devices, clock: common.CompileClock,
                 control: bool = False):
        self.cell = cell
        self.control = control
        self.seed = seed
        self.seconds = seconds
        self.traced = traced
        self.dry = dry
        self.devices = devices
        self.clock = clock
        self.spans = common.Spans(annotate=traced)
        self.setup_s: Optional[float] = None
        self.tracer: Optional[trace.Tracer] = None
        self.trace_dir: Optional[str] = None
        self._c0: Dict[str, float] = {}
        self.window_compiles: Dict[str, float] = {}

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - _T_START

    def window_opened(self, t0: float) -> None:
        self._c0 = self.clock.snapshot()
        if self.traced:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            self.tracer = trace.Tracer(self.trace_dir)
            self.tracer.start()

    def window_tick(self) -> None:
        if self.tracer is not None:
            self.tracer.tick()

    def window_closed(self) -> None:
        if self.tracer is not None:
            self.tracer.stop()
        c1 = self.clock.snapshot()
        self.window_compiles = {k: c1[k] - self._c0.get(k, 0) for k in c1}


def parse(argv: List[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def _metric(value: float, unit: str) -> Dict[str, Any]:
    return {"value": value, "unit": unit}


def main(argv: List[str], root: Optional[pathlib.Path] = None,
         dry: bool = False, control: bool = False) -> int:
    """Run the cell; ``dry`` (tests only) lets it run on the CPU and marks
    the result as no measurement; ``control`` (``bench/calibrate.py``)
    judges the check's control in the program's place."""
    args = parse(argv)
    root = pathlib.Path(root or _ROOT)
    src = root / "src"
    if src.is_dir() and str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        cell = common.Cell(root, args.workload)
        devices = common.accelerator(cell.chips, dry)
        import repro  # noqa: F401  (the program under test)
    except (common.BenchError, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if args.seconds <= 0:
        print("bench: --seconds must be > 0", file=sys.stderr)
        return 2
    if not dry:
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
    clock = common.CompileClock()
    clock.register()
    run = Run(cell, args.seed, args.seconds, bool(args.trace), dry, devices,
              clock, control)
    out = cell.driver().drive(run)

    device = common.device_record(devices, out["memory_peak_bytes"])
    if args.trace:
        reduced = trace.reduce(run.tracer.path())
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        ctx = dict(out["layer_ctx"])
        ctx.update(trace=reduced, window_compiles=run.window_compiles,
                   peaks=peaks.peaks(device["kind"]))
        metrics = {}
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = _metric(value, m["unit"])
        device.update(busy_s=reduced.busy_mean_s, window_s=reduced.window_s)
        breakdown = {"device_ops": [list(x) for x in reduced.device_ops],
                     "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = (run.setup_s if m["name"] == "setup_s"
                     else out["end_to_end"].get(m["name"]))
            if value is None:
                raise common.BenchError(f"{cell.name}: no value for "
                                        f"{m['name']}")
            metrics[m["name"]] = _metric(value, m["unit"])
        breakdown = None

    result: Dict[str, Any] = {
        "correct": bool(out["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    notes = dict(out["notes"])
    notes.update(setup_s=run.setup_s, window_compiles=run.window_compiles)
    if dry:
        result["dry_run"] = DRY_NOTE
    result["notes"] = notes
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in out["checks"].items()}
    print(json.dumps(result, default=float), flush=True)
    for name, (v, lim) in out["checks"].items():
        print(f"check {name}: {v} limit {lim}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
