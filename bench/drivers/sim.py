"""Simulation cells: a ``PhysicsEngine`` rollout through a ``DeviceSession``.

Each step is the application's own loop, back to back: ``emit_step``
(which runs the host broadphase over the current state and emits the
step's kernels), ``session.submit``, ``session.flush``. Set-up builds the
engine and the session and runs the mix's warm-up steps; the window then
runs whole steps until ``--seconds`` have passed.

The engine allocates a new control buffer per group and step and keeps it,
so the session's arena grows by one row per group a step and its slab
doubles (a new program to build) when the row count passes a power of two.
The warm-up steps therefore run past the last doubling before the window,
so that the window's steps all run at one capacity: with 64 groups, 33
warm-up steps reach 2,112 rows (capacity 4,096), and the window has until
step 64 before the next doubling.

The check keeps the program's state of a few groups, drawn from the seed,
before the first step and after every step, and recomputes each step with
the reference.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict

import numpy as np

from bench import common

#: Groups whose every state the check recomputes.
CHECK_GROUPS = 8


def drive(run) -> Dict[str, Any]:
    from repro.core import DeviceSession, TaskStream
    from repro.sim import ENVIRONMENTS, PhysicsEngine

    cell = run.cell
    conf, mix = cell.config, cell.traffic
    g = int(mix["group_size"])
    n_envs = int(conf["n_envs"])
    s_env, s_sample = common.sub_seeds(run.seed, 2)
    spans = run.spans

    t = time.perf_counter()
    eng = PhysicsEngine(ENVIRONMENTS[conf["env"]], n_envs=n_envs,
                        group_size=g, seed=s_env)
    session = DeviceSession(plan_mode=conf["session"]["plan_mode"])
    n_groups = n_envs // g
    rng = np.random.RandomState(s_sample)
    picked = sorted(rng.permutation(n_groups)[:min(CHECK_GROUPS, n_groups)])
    states = {int(gi): [eng.groups[gi].state.value] for gi in picked}

    def one_step() -> int:
        stream = TaskStream()
        with spans("emit_step"):
            eng.emit_step(stream)
        with spans("submit"):
            session.submit(stream.tasks)
        with spans("flush"):
            session.flush()
        for gi in picked:
            states[int(gi)].append(eng.groups[gi].state.value)
        return len(stream.tasks)

    phases = {"engine_s": time.perf_counter() - t, "warm_up_steps_s": []}
    for _ in range(int(mix["warmup_steps"])):
        t = time.perf_counter()
        one_step()
        phases["warm_up_steps_s"].append(time.perf_counter() - t)
    run.setup_done()

    c0 = session.session_stats()
    t0 = time.perf_counter()
    run.window_opened(t0)
    spans.recording = True
    steps = tasks = 0
    while True:
        tasks += one_step()
        steps += 1
        run.window_tick()
        if time.perf_counter() - t0 >= run.seconds:
            break
    t1 = time.perf_counter()
    spans.recording = False
    run.window_closed()
    c1 = session.session_stats()
    peak = common.peak_bytes(run.devices)
    active = eng.stats.active_contacts[-steps * n_groups:]
    session.close()

    traj = {gi: [np.asarray(v, np.float32) for v in vals]
            for gi, vals in states.items()}
    nonfinite = sum(int(not np.isfinite(t[-1]).all()) for t in traj.values())
    del eng, session, states
    gc.collect()

    got = cell.reference().compare(conf, s_env, g, traj, control=run.control)
    step_error = got["max_step_error"]
    if run.control:
        # The control's step stands in the program's place; the program's
        # reading is a note.
        step_error = got["control_max_step_error"]
    limits = conf["check"]["limits"]
    checks = {
        "initial_state_error": (got["initial_state_error"], 0.0),
        "max_step_error": (step_error, limits["max_step_error"]),
        "nonfinite_groups": (nonfinite, 0),
    }
    correct = all(v <= lim for v, lim in checks.values())
    window_s = t1 - t0
    return {
        "end_to_end": {"env_steps_per_s": steps * n_envs / window_s},
        "layer_ctx": {
            "kind": "sim", "chips": cell.chips, "spans": spans,
            "counters": (c0, c1), "tasks_in_window": tasks,
            "steps_in_window": steps, "window_s": window_s,
        },
        "notes": {
            "steps_in_window": steps, "tasks_in_window": tasks,
            "window_s": window_s,
            "tasks_per_step": tasks / steps,
            "active_contacts_mean": float(np.mean(active)) if active else None,
            "contact_candidates": eng_candidates(conf),
            "arena_live_rows": c1.get("arena_live_rows"),
            "plan_cache_hits_in_window": (c1["plan_cache_hits"]
                                          - c0["plan_cache_hits"]),
            "checked_steps": len(next(iter(traj.values()))) - 1,
            "checked_groups": len(traj),
            "program_max_step_error": (got["max_step_error"] if run.control
                                       else None),
            "setup_phases": phases,
        },
        "checks": checks, "correct": correct,
        "attempted": steps, "failed": nonfinite,
        "memory_peak_bytes": peak,
    }


def eng_candidates(conf) -> int:
    n = conf["body"]["n_bodies"]
    return n * (n - 1) // 2 - len(conf["body"]["joints"])
