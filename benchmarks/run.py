"""Benchmark runner: one section per paper table/figure (DESIGN.md §8).
Prints ``name,metric,value`` CSV. Usage:
    PYTHONPATH=src python -m benchmarks.run [--flag=value ...] [section ...]

Flags (consumed by sections via benchmarks.common):
  --window=N       ACS window size
  --streams=K      thread count for the threaded scheduler
  --inflight=M     frontier scheduler's in-flight group cap
  --plan-mode=P    device runner plan lowering: wave | frontier | loop
  --scheduler=S    restrict comparison sections to serial + S
  --json=PATH      also write every emitted row (plus flags and per-section
                   timings) as machine-readable JSON — the BENCH_*.json
                   perf-trajectory format CI uploads as an artifact
  --smoke          CI-sized inputs; defaults to the plan-lowering sections
"""

from __future__ import annotations

import json
import sys
import time

from . import (
    bench_dag_overhead,
    bench_depcheck,
    bench_device,
    bench_dynamic_dnn,
    bench_frontier,
    bench_mesh_scaling,
    bench_moe_waves,
    bench_occupancy,
    bench_qos,
    bench_rl_e2e,
    bench_serving,
    bench_sim_speedup,
    bench_soak,
    bench_static_dnn,
    bench_window_size,
    common,
)

SECTIONS = {
    "depcheck": bench_depcheck,          # Table II
    "dag_overhead": bench_dag_overhead,  # Fig 9
    "sim_speedup": bench_sim_speedup,    # Figs 21/22
    "rl_e2e": bench_rl_e2e,              # Fig 23
    "occupancy": bench_occupancy,        # Figs 2/24
    "dynamic_dnn": bench_dynamic_dnn,    # Figs 25/26
    "static_dnn": bench_static_dnn,      # Figs 27/28
    "window_size": bench_window_size,    # Fig 29
    "moe_waves": bench_moe_waves,        # beyond-paper (DESIGN §4)
    "frontier": bench_frontier,          # beyond-paper (DESIGN §9)
    "device": bench_device,              # ACS-HW analogue (DESIGN §2 A3)
    "serving": bench_serving,            # live sessions (DESIGN §10)
    "soak": bench_soak,                  # lifetime invariants (DESIGN §2 A3)
    "mesh_scaling": bench_mesh_scaling,  # mesh-sharded window (DESIGN §12)
    "qos": bench_qos,                    # multi-tenant QoS plane (DESIGN §13)
}

# The sections --smoke runs when none are named: the ones exercising plan
# lowering, the unified scheduler API, the live-session serving path, and
# the scoreboard dependency engine (depcheck's probe-vs-scan counters and
# window_size's window=256 leg over the real sim/dyn streams) — so
# regressions there fail in CI, not at bench time.
SMOKE_SECTIONS = ("depcheck", "device", "frontier", "serving",
                  "window_size", "mesh_scaling", "qos")


def main() -> None:
    chosen = []
    json_path = None
    for arg in sys.argv[1:]:
        if arg == "--smoke":
            common.OPTIONS["smoke"] = "1"
        elif arg.startswith("--json="):
            json_path = arg[len("--json="):]
            if not json_path:
                raise SystemExit("--json expects a path (--json=bench.json)")
        elif arg.startswith("--") and "=" in arg:
            key, _, value = arg[2:].partition("=")
            if key in common.FLAG_KEYS:
                if not value.isdigit() or int(value) < 1:
                    raise SystemExit(f"--{key} expects a positive integer, got {value!r}")
            elif key in common.CHOICE_FLAGS:
                allowed = common.CHOICE_FLAGS[key]
                if value not in allowed:
                    raise SystemExit(
                        f"--{key} expects one of {{{', '.join(allowed)}}}, got {value!r}"
                    )
            else:
                flags = [f"--{k}=N" for k in common.FLAG_KEYS]
                flags += [f"--{k}={{{'|'.join(v)}}}" for k, v in common.CHOICE_FLAGS.items()]
                raise SystemExit(
                    f"unknown flag --{key}; choose from: "
                    + ", ".join(flags + ["--json=PATH", "--smoke"])
                )
            common.OPTIONS[key] = value
        elif arg.startswith("--"):
            raise SystemExit(
                f"malformed flag {arg!r}: flags take --name=value form "
                "(e.g. --scheduler=frontier); --smoke is the only bare flag"
            )
        else:
            chosen.append(arg)
    unknown = [n for n in chosen if n not in SECTIONS]
    if unknown:
        raise SystemExit(
            f"unknown section(s) {unknown}; choose from: {', '.join(SECTIONS)}"
        )
    if not chosen:
        chosen = list(SMOKE_SECTIONS) if common.smoke() else list(SECTIONS)
    print("section,metric,value")
    timings = {}
    for name in chosen:
        mod = SECTIONS[name]
        t0 = time.time()
        mod.main()
        timings[name] = round(time.time() - t0, 1)
        print(f"_timing,{name}_seconds,{timings[name]}")
    if json_path is not None:
        payload = {
            "flags": dict(common.OPTIONS),
            "sections": chosen,
            "timings_seconds": timings,
            "results": common.RESULTS,
        }
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"_json,path,{json_path}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
