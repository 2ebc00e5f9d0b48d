"""The simulation engine: physics as a stream of small ACS kernels.

Faithful workload structure (paper §II-B): each step of each environment
group emits
  * one ``joint_solve`` kernel per joint           (spring-damper + actuation)
  * one ``contact_pair`` kernel per *active* pair  (INPUT-DEPENDENT: the
    active set comes from a host-side broadphase over the current state —
    this is what makes the computational graph vary per input/state)
  * one ``ground_contact`` kernel per group
  * one ``integrate`` kernel per group             (gather forces, Euler)
  * one ``observe`` kernel per group               (policy features)

Kernels are deliberately small (a group is ``group_size`` envs × ≤14
bodies ≈ hundreds of floats) — the paper's small-kernel property. Groups
use disjoint buffers, so ACS's window recovers cross-group and intra-step
parallelism that the serial stream hides.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.buffers import Buffer, BufferPool
from ..core.spans import span
from ..core.task import Task
from ..core.wrapper import AcsKernel, TaskStream
from .envs import EnvSpec, initial_state

__all__ = ["PhysicsEngine", "SimKernelStats", "SIM_KERNELS", "register_device_kernels"]

_DT = 0.01
_GRAVITY = -9.81
_KP, _KD = 80.0, 4.0  # joint spring-damper
_KC = 200.0  # contact penalty stiffness
_KG = 400.0  # ground stiffness


# --------------------------------------------------------------------------
# Kernel bodies (pure jnp; statics appended by the wrapper)
# --------------------------------------------------------------------------

def _joint_fn(state, ctrl, j, parent, child, rest, kp, kd):
    """Spring-damper + actuation along the joint axis. [g,B,6] -> [1,g,6]
    (force-on-parent ++ force-on-child)."""
    pos, vel = state[..., :3], state[..., 3:]
    d = pos[:, child] - pos[:, parent]  # [g, 3]
    dist = jnp.linalg.norm(d, axis=-1, keepdims=True) + 1e-6
    u = d / dist
    rel_v = vel[:, child] - vel[:, parent]
    f = (kp * (dist - rest) + kd * jnp.sum(rel_v * u, axis=-1, keepdims=True)) * u
    f = f + ctrl[:, j : j + 1] * u  # actuation torque proxy along the axis
    return jnp.concatenate([f, -f], axis=-1)[None]  # [1, g, 6]


def _contact_fn(state, a, b, radius, kc):
    """Sphere-sphere penalty. [g,B,6] -> [1,g,6] (force-on-a ++ force-on-b)."""
    pos, vel = state[..., :3], state[..., 3:]
    d = pos[:, b] - pos[:, a]
    dist = jnp.linalg.norm(d, axis=-1, keepdims=True) + 1e-6
    u = d / dist
    pen = jnp.maximum(2.0 * radius - dist, 0.0)
    rel_v = jnp.sum((vel[:, b] - vel[:, a]) * u, axis=-1, keepdims=True)
    f = -(kc * pen - 0.1 * kc * pen * rel_v) * u  # push a away from b
    return jnp.concatenate([f, -f], axis=-1)[None]


def _ground_fn(state, radius, kg):
    """Ground-plane penalty + tangential damping. [g,B,6] -> [g,B,3]."""
    pos, vel = state[..., :3], state[..., 3:]
    pen = jnp.maximum(radius - pos[..., 2:3], 0.0)
    fz = kg * pen - 2.0 * jnp.minimum(vel[..., 2:3], 0.0) * kg * pen
    in_contact = (pen > 0).astype(state.dtype)
    ft = -5.0 * vel[..., :2] * in_contact  # friction proxy
    return jnp.concatenate([ft, fz], axis=-1)


def _integrate_fn(state, jf, gf, *cf_rows_and_statics):
    """Gather all force contributions, semi-implicit Euler step."""
    (parents, children, pairs_a, pairs_b, n_cf, mass, dt) = cf_rows_and_statics[-7:]
    cf_rows = cf_rows_and_statics[:-7]
    assert len(cf_rows) == n_cf
    g, b = state.shape[0], state.shape[1]
    force = jnp.zeros((g, b, 3), state.dtype)
    force = force + gf
    parents = np.asarray(parents, np.int32)
    children = np.asarray(children, np.int32)
    # jf: [J, g, 6] -> per-body scatter-add
    jf_t = jnp.swapaxes(jf, 0, 1)  # [g, J, 6]
    force = force.at[:, parents].add(jf_t[..., :3])
    force = force.at[:, children].add(jf_t[..., 3:])
    if cf_rows:
        cf = jnp.concatenate(cf_rows, axis=0)  # [C, g, 6]
        cf_t = jnp.swapaxes(cf, 0, 1)  # [g, C, 6]
        force = force.at[:, np.asarray(pairs_a, np.int32)].add(cf_t[..., :3])
        force = force.at[:, np.asarray(pairs_b, np.int32)].add(cf_t[..., 3:])
    acc = force / mass + jnp.array([0.0, 0.0, _GRAVITY], state.dtype)
    vel = state[..., 3:] + dt * acc
    pos = state[..., :3] + dt * vel
    return jnp.concatenate([pos, vel], axis=-1)


def _observe_fn(state):
    """Policy features: per-env flatten of (pos - torso, vel). [g,B,6] -> [g,B*6]."""
    torso = state[:, :1, :3]
    rel = jnp.concatenate([state[..., :3] - torso, state[..., 3:]], axis=-1)
    return rel.reshape(state.shape[0], -1)


def _joint_flops(inputs, outputs, *s):
    g = inputs[0].shape[0] if hasattr(inputs[0], "shape") else 1
    return 60.0 * g


_JOINT = AcsKernel(name="joint_solve", fn=_joint_fn)
_CONTACT = AcsKernel(name="contact_pair", fn=_contact_fn)
_GROUND = AcsKernel(name="ground_contact", fn=_ground_fn)
_INTEGRATE = AcsKernel(name="integrate", fn=_integrate_fn)
_OBSERVE = AcsKernel(name="observe", fn=_observe_fn)

#: Every kernel a PhysicsEngine stream can emit — the fixed opcode set the
#: device-resident window (DESIGN §2 A3) needs registered ahead of time.
SIM_KERNELS = (_JOINT, _CONTACT, _GROUND, _INTEGRATE, _OBSERVE)

#: Switch-branch table for the device ready-queue fast path: empty on
#: purpose. Every sim kernel either changes the row geometry (observe
#: flattens [g,B,6] -> [g,B*6]) or spans multiple shape classes per
#: stream (joint/contact/ground group sizes differ), so none satisfies
#: the single-class, shape-preserving eligibility of
#: ``kernels/ready_queue.py``. Sim epochs run through the structurally
#: identical ``lax.while_loop`` interpreter — still one dispatch.
SWITCH_BRANCHES: Dict[str, object] = {}


def register_device_kernels(registry) -> Dict[str, int]:
    """Register the simulation kernel set with a
    :class:`~repro.core.DeviceOpRegistry` (fn-less: the arena path executes
    each task's wrapper-resolved callable, with static args baked; the
    registry entry is the opcode-table slot that gates lowering). Returns
    name -> opcode. Shape classes per opcode are recorded by the lowering
    pass in ``registry.classes_seen``."""
    for name, fn in SWITCH_BRANCHES.items():
        registry.register_switch_branch(name, fn)
    return {k.name: registry.register(k.name) for k in SIM_KERNELS}


class SimKernelStats:
    """Per-stream kernel census (reproduces the paper's Figs 3-5 metrics)."""

    def __init__(self) -> None:
        self.kernels = 0
        self.steps = 0
        self.elements: List[int] = []  # per-kernel output element counts
        self.active_contacts: List[int] = []
        self.candidate_contacts = 0

    @property
    def kernels_per_step(self) -> float:
        return self.kernels / max(self.steps, 1)

    def cta_histogram(self, threads_per_cta: int = 256) -> Dict[int, int]:
        """Kernel-size distribution in CTAs (elements/threads ceil) — Fig 5."""
        hist: Dict[int, int] = {}
        for e in self.elements:
            ctas = max(1, -(-e // threads_per_cta))
            hist[ctas] = hist.get(ctas, 0) + 1
        return hist

    def as_dict(self) -> Dict[str, float]:
        return {
            "kernels": self.kernels,
            "steps": self.steps,
            "kernels_per_step": self.kernels_per_step,
            "mean_kernel_elems": float(np.mean(self.elements)) if self.elements else 0.0,
            "mean_active_contacts": float(np.mean(self.active_contacts))
            if self.active_contacts
            else 0.0,
        }


@dataclasses.dataclass
class _Group:
    state: Buffer
    jf: Buffer
    gf: Buffer
    cf: Buffer
    obs: Buffer


class PhysicsEngine:
    """One environment family, ``n_envs`` instances in groups of
    ``group_size`` (disjoint buffer sets => schedulable in parallel)."""

    def __init__(
        self,
        spec: EnvSpec,
        n_envs: int = 64,
        group_size: int = 8,
        seed: int = 0,
        dt: float = _DT,
        broadphase_margin: float = 0.25,
    ):
        assert n_envs % group_size == 0
        self.spec = spec
        self.n_envs = n_envs
        self.group_size = group_size
        self.dt = dt
        self.margin = broadphase_margin
        self.pool = BufferPool()
        self.rng = np.random.RandomState(seed)
        self.candidates = spec.contact_candidates()
        self.stats = SimKernelStats()
        self._step_index = 0

        g, b, j, c = group_size, spec.n_bodies, spec.n_joints, len(self.candidates)
        full = initial_state(spec, n_envs, seed)
        self.groups: List[_Group] = []
        for gi in range(n_envs // group_size):
            sl = full[gi * g : (gi + 1) * g]
            self.groups.append(
                _Group(
                    state=self.pool.alloc((g, b, 6), np.float32, f"state{gi}", jnp.asarray(sl)),
                    jf=self.pool.alloc((max(j, 1), g, 6), np.float32, f"jf{gi}",
                                       jnp.zeros((max(j, 1), g, 6), jnp.float32)),
                    gf=self.pool.alloc((g, b, 3), np.float32, f"gf{gi}",
                                       jnp.zeros((g, b, 3), jnp.float32)),
                    cf=self.pool.alloc((max(c, 1), g, 6), np.float32, f"cf{gi}",
                                       jnp.zeros((max(c, 1), g, 6), jnp.float32)),
                    obs=self.pool.alloc((g, b * 6), np.float32, f"obs{gi}",
                                        jnp.zeros((g, b * 6), jnp.float32)),
                )
            )

    # -- broadphase (host side; the source of input-dependence) ------------
    def _active_pairs(self, group: _Group) -> List[int]:
        with span("sim.broadphase"):
            pos = np.asarray(group.state.value)[..., :3]  # [g, B, 3]
            thresh = 2.0 * self.spec.radius + self.margin
            act = []
            for ci, (a, b) in enumerate(self.candidates):
                d = np.linalg.norm(pos[:, b] - pos[:, a], axis=-1)
                if np.any(d < thresh):
                    act.append(ci)
            return act

    # -- emission -----------------------------------------------------------
    def emit_step(self, stream: TaskStream, policy: Optional[Callable] = None) -> None:
        """Launch one simulation step's kernels for every group, exactly as
        an application would: per-group, program order, single stream."""
        with span("sim.emit"):
            spec, g = self.spec, self.group_size
            for gi, grp in enumerate(self.groups):
                # fresh ctrl buffer per (group, step): host-produced actions
                if policy is not None:
                    actions = np.asarray(policy(np.asarray(grp.obs.value)), np.float32)
                else:
                    actions = self.rng.uniform(-1, 1, size=(g, spec.n_joints)).astype(np.float32)
                ctrl = self.pool.alloc(
                    (g, spec.n_joints), np.float32,
                    f"ctrl{gi}_s{self._step_index}", jnp.asarray(actions),
                )

                for j, (p, c) in enumerate(spec.joints):
                    # reads full state + this joint's control column;
                    # writes its OWN jf row -> joints are mutually independent.
                    _JOINT.launch(
                        stream,
                        inputs=(grp.state, ctrl),
                        outputs=(grp.jf.row_view(j, 1),),
                        static_args=(j, p, c, 0.35, _KP, _KD),
                    )

                active = self._active_pairs(grp)
                self.stats.active_contacts.append(len(active))
                for ci in active:
                    a, b = self.candidates[ci]
                    _CONTACT.launch(
                        stream,
                        inputs=(grp.state,),
                        outputs=(grp.cf.row_view(ci, 1),),
                        static_args=(a, b, spec.radius, _KC),
                    )

                _GROUND.launch(
                    stream, inputs=(grp.state,), outputs=(grp.gf,),
                    static_args=(spec.radius, _KG),
                )

                parents = tuple(p for p, _ in spec.joints)
                children = tuple(c for _, c in spec.joints)
                pa = tuple(self.candidates[ci][0] for ci in active)
                pb = tuple(self.candidates[ci][1] for ci in active)
                _INTEGRATE.launch(
                    stream,
                    inputs=(grp.state, grp.jf, grp.gf) + tuple(grp.cf.row_view(ci, 1) for ci in active),
                    outputs=(grp.state,),
                    static_args=(parents, children, pa, pb, len(active), spec.mass, self.dt),
                )
                _OBSERVE.launch(stream, inputs=(grp.state,), outputs=(grp.obs,))

            self.stats.kernels = len(stream.tasks)
            self.stats.steps += 1
            self.stats.candidate_contacts = len(self.candidates)
            self._step_index += 1

    def emit_batch(self, stream: TaskStream, n_steps: int,
                   policy: Optional[Callable] = None) -> None:
        for _ in range(n_steps):
            self.emit_step(stream, policy)

    def record_kernel_sizes(self, stream: TaskStream) -> None:
        from ..core.task import operand_shape

        for t in stream.tasks:
            elems = sum(int(np.prod(operand_shape(o))) for o in t.outputs)
            self.stats.elements.append(elems)

    def buffers(self) -> Tuple[Buffer, ...]:
        """All live allocations (states, force accumulators, controls) in
        allocation order — what the device runner's slab arena packs."""
        return self.pool.buffers()

    def state_snapshot(self) -> np.ndarray:
        return np.concatenate([np.asarray(g.state.value) for g in self.groups], axis=0)
