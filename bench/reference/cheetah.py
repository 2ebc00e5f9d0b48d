"""Plain reference of one step of the articulated point-mass physics, in
NumPy float32, with the broadphase.

Each body is a point mass with a collision radius. A step: for every
joint a spring-damper force plus the actuation along its axis; for every
body pair not joined, if the pair comes within ``2 * radius + margin`` in
any environment of the group (the broadphase), a penalty force with
damping; a ground penalty with friction; then semi-implicit Euler.
Everything the step needs is in the configuration's file.

The check is teacher-forced: each step the program took is recomputed
from the program's own state before it, so the chaotic growth of small
differences over a long rollout never enters; the state before the first
step and the actions are drawn from the seed here, as the engine draws
them. The control rounds every intermediate of the same step to
bfloat16, the step below the configuration's float32.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List

import ml_dtypes
import numpy as np

F32 = np.float32


def _keep(a):
    return np.asarray(a, F32)


def _bf16(a):
    return np.asarray(a, F32).astype(ml_dtypes.bfloat16).astype(F32)


def candidates(n_bodies: int, joints) -> List[tuple]:
    joined = {tuple(sorted(j)) for j in joints}
    return [(a, b) for a in range(n_bodies) for b in range(a + 1, n_bodies)
            if (a, b) not in joined]


def initial_state(conf: Dict[str, Any], seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    n, b = conf["n_envs"], conf["body"]["n_bodies"]
    pos = rng.uniform(-0.5, 0.5, size=(n, b, 3)).astype(F32)
    pos[..., 2] += 1.0
    vel = 0.1 * rng.randn(n, b, 3).astype(F32)
    return np.concatenate([pos, vel], axis=-1)


def actions(conf: Dict[str, Any], seed: int, group_size: int,
            steps: int) -> np.ndarray:
    """[steps, groups, group_size, joints]: uniform in [-1, 1), drawn
    group by group, step by step."""
    rng = np.random.RandomState(seed)
    groups = conf["n_envs"] // group_size
    nj = len(conf["body"]["joints"])
    out = np.empty((steps, groups, group_size, nj), F32)
    for s in range(steps):
        for g in range(groups):
            out[s, g] = rng.uniform(-1, 1, size=(group_size, nj)).astype(F32)
    return out


def step(conf: Dict[str, Any], state: np.ndarray, ctrl: np.ndarray,
         r: Callable = _keep) -> np.ndarray:
    """One step of one group: state [g, B, 6], ctrl [g, J] -> [g, B, 6].
    ``r`` rounds each intermediate (identity: float32)."""
    body, ph = conf["body"], conf["physics"]
    joints = [tuple(j) for j in body["joints"]]
    radius, mass = F32(body["radius"]), F32(body["mass"])
    state = r(state)
    pos, vel = state[..., :3], state[..., 3:]
    force = np.zeros(pos.shape, F32)

    def axis(a, b):
        d = r(pos[:, b] - pos[:, a])
        dist = r(np.linalg.norm(d, axis=-1, keepdims=True) + F32(1e-6))
        return d, dist, r(d / dist)

    for j, (p, c) in enumerate(joints):
        _, dist, u = axis(p, c)
        rel_v = r(vel[:, c] - vel[:, p])
        mag = r(F32(ph["kp"]) * r(dist - F32(ph["rest"]))
                + F32(ph["kd"]) * r(np.sum(r(rel_v * u), axis=-1, keepdims=True)))
        f = r(r(mag * u) + r(ctrl[:, j:j + 1] * u))
        force[:, p] += f
        force[:, c] -= f

    thresh = 2.0 * body["radius"] + ph["margin"]
    for a, b in candidates(body["n_bodies"], joints):
        if not np.any(np.linalg.norm(state[:, b, :3] - state[:, a, :3],
                                     axis=-1) < thresh):
            continue
        _, dist, u = axis(a, b)
        pen = r(np.maximum(F32(2.0) * radius - dist, F32(0.0)))
        rel_v = r(np.sum(r((vel[:, b] - vel[:, a]) * u), axis=-1, keepdims=True))
        kc = F32(ph["kc"])
        f = r(-r(r(kc * pen) - r(F32(0.1) * kc * pen * rel_v)) * u)
        force[:, a] += f
        force[:, b] -= f

    kg = F32(ph["kg"])
    pen = r(np.maximum(radius - pos[..., 2:3], F32(0.0)))
    fz = r(r(kg * pen) - r(F32(2.0) * np.minimum(vel[..., 2:3], F32(0.0)) * kg * pen))
    ft = r(F32(-5.0) * vel[..., :2] * (pen > 0).astype(F32))
    force = r(force + np.concatenate([ft, fz], axis=-1))

    acc = r(force / mass + np.asarray([0.0, 0.0, ph["gravity"]], F32))
    dt = F32(ph["dt"])
    vel = r(vel + r(dt * acc))
    pos = r(pos + r(dt * vel))
    return np.concatenate([pos, vel], axis=-1)


def _error(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(got - ref)) / max(1.0, float(np.max(np.abs(ref)))))


def compare(conf: Dict[str, Any], seed: int, group_size: int,
            states: Dict[int, List[np.ndarray]],
            control: bool = False) -> Dict[str, float]:
    """``states[g]`` is the program's state of group ``g`` before its first
    step and after each step. Returns ``initial_state_error`` (exact
    inputs: 0), ``max_step_error`` (the worst step's largest difference
    over the reference state's largest magnitude, at least 1), and with
    ``control`` the same for the bfloat16 step."""
    steps = len(next(iter(states.values()))) - 1
    init = initial_state(conf, seed)
    acts = actions(conf, seed, group_size, steps)
    out = {"initial_state_error": 0.0, "max_step_error": 0.0}
    if control:
        out["control_max_step_error"] = 0.0
    for g, traj in states.items():
        want0 = init[g * group_size:(g + 1) * group_size]
        out["initial_state_error"] = max(out["initial_state_error"],
                                         float(np.max(np.abs(traj[0] - want0))))
        for s in range(steps):
            ref = step(conf, traj[s], acts[s, g])
            out["max_step_error"] = max(out["max_step_error"],
                                        _error(traj[s + 1], ref))
            if control:
                low = step(conf, traj[s], acts[s, g], _bf16)
                out["control_max_step_error"] = max(
                    out["control_max_step_error"], _error(low, ref))
    return out
