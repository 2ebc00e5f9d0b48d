"""Dynamic-DNN inference through ACS (paper §VI-B): classify a stream of
images with an InstaNAS-like instance-aware CNN whose architecture — and
therefore kernel stream — changes per image. The per-input graphs defeat
ahead-of-time DAG frameworks; ACS schedules each one at runtime while its
wave-signature cache keeps compilation amortized across inputs.

    PYTHONPATH=src python examples/dynamic_dnn_inference.py [n_images]
"""

import sys
import time

import numpy as np

from repro.core import TaskStream, WaveScheduler
from repro.dyn import WORKLOADS
from repro.dyn.instanas import controller


def main():
    n_images = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    init_fn, build_fn, _ = WORKLOADS["instanas"]
    params = init_fn(seed=0)
    sched = WaveScheduler(window_size=32)
    rng = np.random.RandomState(0)

    prev_dispatches = 0
    for i in range(n_images):
        x = rng.randn(1, 3, 32, 32).astype(np.float32) * (1 + 0.5 * i)
        active = sum(sum(m) for m in controller(x))
        stream = TaskStream()
        out = build_fn(params, stream, x)
        t0 = time.perf_counter()
        report = sched.run(stream.tasks)
        dt = (time.perf_counter() - t0) * 1e3
        dispatches = report.exec_stats["dispatches"] - prev_dispatches
        prev_dispatches = report.exec_stats["dispatches"]
        pred = int(np.argmax(np.asarray(out.value)))
        print(f"image {i}: {active:2d} blocks active, "
              f"{len(stream.tasks):3d} kernels -> "
              f"{dispatches:3d} dispatches, "
              f"class={pred}, {dt:.0f}ms")

    exec_stats = sched.executor.stats
    print(f"\nwave-program compiles across all inputs: {exec_stats.compiles} "
          f"(signature cache absorbs per-input graph variation)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
