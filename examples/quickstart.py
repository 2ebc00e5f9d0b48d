"""Quickstart: ACS in 60 seconds.

Build an irregular, input-dependent task stream (a tiny physics step),
run it serially (the single-stream baseline) and through the ACS window,
and watch the dispatch count collapse while results stay identical.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import TaskStream, WaveScheduler, run_serial
from repro.sim import PhysicsEngine, make_env


def build(seed):
    eng = PhysicsEngine(make_env("ant"), n_envs=16, group_size=4, seed=seed)
    stream = TaskStream()
    eng.emit_step(stream)
    return eng, stream


def main():
    # 1. serial baseline: one dispatch per kernel, program order
    eng_a, stream_a = build(seed=7)
    serial = run_serial(stream_a.tasks)

    # 2. ACS: windowed out-of-order scheduling -> fused waves
    eng_b, stream_b = build(seed=7)
    acs = WaveScheduler(window_size=32).run(stream_b.tasks)

    a, b = eng_a.state_snapshot(), eng_b.state_snapshot()
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    print(f"kernels launched      : {len(stream_a.tasks)}")
    print(f"serial dispatches     : {serial.exec_stats['dispatches']}")
    print(f"ACS dispatches        : {acs.exec_stats['dispatches']}")
    print(f"ACS mean wave width   : {acs.mean_wave_width:.1f}")
    print(f"max wave width        : {acs.exec_stats['max_wave_width']}")
    print(f"results identical     : True")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
