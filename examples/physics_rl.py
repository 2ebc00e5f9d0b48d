"""End-to-end deep-RL data generation through ACS (the paper's headline
workload): run Brax-style physics environments with a linear policy,
collecting a batch of (obs, action, reward-proxy) trajectories — the
simulation stream scheduled by the ACS window, exactly as §VI-A.

    PYTHONPATH=src python examples/physics_rl.py [env] [steps] [scheduler]

``scheduler`` is one of serial | wave | threaded | frontier | device
(default wave; see ``repro.core.SCHEDULER_NAMES``; ``device`` is the
ACS-HW analogue — the whole step's stream in ONE dispatch through the
slab arena). Each RL step emits a fresh,
input-dependent kernel graph, so this is the frontier scheduler's home
turf: per-kernel compile caches carry across steps while wave-shaped
caches keep missing.
"""

import sys
import time

import numpy as np

from repro.core import TaskStream, make_scheduler
from repro.sim import PhysicsEngine, make_env


def main():
    env = sys.argv[1] if len(sys.argv) > 1 else "cheetah"
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    sched_name = sys.argv[3] if len(sys.argv) > 3 else "wave"
    try:
        run = make_scheduler(sched_name)
    except ValueError as exc:
        raise SystemExit(str(exc))

    eng = PhysicsEngine(make_env(env), n_envs=16, group_size=4, seed=0)
    rng = np.random.RandomState(0)

    obs_dim = eng.spec.n_bodies * 6
    w_policy = rng.randn(obs_dim, eng.spec.n_joints).astype(np.float32) * 0.1

    def policy(obs):  # linear policy over engine observations
        return np.tanh(obs @ w_policy)

    trajectory = []
    t0 = time.perf_counter()
    for step in range(steps):
        stream = TaskStream()
        eng.emit_step(stream, policy=policy)
        report = run(stream.tasks)
        snap = eng.state_snapshot()
        reward = -np.linalg.norm(snap[..., :3], axis=-1).mean()  # stay near origin
        stats = report.exec_stats
        extra = ""
        if report.groups:  # frontier: show the async profile
            extra = (f" syncs={stats['blocking_syncs']}"
                     f" inflight={report.max_inflight_groups()}")
        print(f"step {step}: kernels={len(stream.tasks)} "
              f"dispatches={stats['dispatches']} "
              f"wave_width={report.mean_wave_width:.1f} reward={reward:.3f}{extra}")
        trajectory.append(reward)
    dt = time.perf_counter() - t0
    print(f"\n{env} [{sched_name}]: {steps} steps, {dt:.2f}s wall, "
          f"states finite: {bool(np.all(np.isfinite(eng.state_snapshot())))}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
