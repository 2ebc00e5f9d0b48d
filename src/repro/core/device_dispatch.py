"""ACS-HW analogue: the scheduling window lives on the device (DESIGN §2 A3).

The paper's ACS-HW moves the window into GPU hardware so that kernel
completion -> upstream update -> ready dispatch never round-trips to the
CPU. A TPU has no command processor we can extend, so the TPU-idiomatic
equivalent is a *device-resident window interpreter*:

1. The host runs the (cheap, windowed) dependency analysis ONCE per stream
   and emits a plan (wave-synchronous or frontier-grouped — `plan_waves` /
   `plan_frontier`), then lowers it over a **shape-class slab arena**
   (`core/arena.py`): every step is one homogeneous task group with a
   static ``(opcode, arity, input/output shape classes)`` spec plus dense
   int32 row tables — the moral equivalent of the upstream-id SRAM tables
   of Fig 20, generalized from one uniform ``(D,)`` shape to the real
   sim/dyn workloads (mixed shapes and dtypes, variable arity, row-view
   aliasing, multi-output tasks).
2. A single compiled program walks the steps (runs of identical step specs
   are compressed into ``lax.scan``s), gathering operand rows from the
   per-class slabs (cross-class gathers — inputs and outputs of one step
   may live in different slabs), applying the step's kernel (vmapped over
   the group), and scattering results back.

Host involvement: ONE dispatch for the whole stream — vs one per kernel
(serial) or one per wave (ACS-SW). This is exactly the communication
reduction ACS-HW claims, realized with jax control flow instead of SRAM
next to a command processor.

:class:`DeviceWindowRunner` is the *closed-batch* form: each ``run`` plans,
lowers, packs a fresh arena, and dispatches once. :class:`DeviceSession`
is the *persistent* form (DESIGN §2 A3): a live
:class:`~.session.SchedulerSession` whose window accepts ``submit``-ed
tasks at any time and drains them in **epochs** — each epoch lowers only
the newly admitted window slice against a session-lifetime
:class:`~.arena.SlabArena` (slabs stay device-resident across epochs;
host values re-sync only at retire boundaries) with a structure-keyed plan
cache at session scope, so recurring stream shapes skip re-lowering
entirely. That is the rolling-window half of ACS-HW the per-stream runner
cannot express: the dependency state and the operands live beside the
device for the whole program, and a new submission costs one epoch
dispatch, not a re-plan/repack of the world.

The seed's uniform-shape interpreter survives as the *legacy path*
(`compile_wave_plan` + `DeviceWindowRunner.execute_uniform`): operands
must share one padded shape ``(D,)``, opcodes must be arity-<=3 registry
branches. It now refuses over-arity tasks loudly instead of silently
truncating operand lists.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .arena import SlabArena, row_capacity
from .buffers import Buffer, BufferView
from .executors import ExecStats, SerialExecutor, group_by_signature
from .scheduler import PLAN_MODES, SchedulerReport
from .scoreboard import dependency_arrays
from .session import SchedulerSession
from .task import Task, operand_base, operand_shape
from .window import SchedulingWindow

__all__ = [
    "DeviceOpRegistry",
    "compile_wave_plan",
    "plan_waves",
    "plan_frontier",
    "plan_active_fraction",
    "lower_plan",
    "lower_epoch_program",
    "EpochProgram",
    "DeviceStep",
    "DeviceWindowRunner",
    "DeviceSession",
]

MAX_ARITY = 3  # legacy uniform-slab path only; the arena path has no limit


class DeviceOpRegistry:
    """The device interpreter's fixed opcode table (the paper's HW window
    supports a finite kernel set burned in next to the command processor).

    ``register`` assigns each kernel name a stable opcode. ``strict``
    registries refuse to lower tasks whose opcode was never registered —
    the faithful HW behaviour; non-strict registries auto-register on
    first sight (the software-managed table `make_scheduler("device")`
    uses, so any workload runs out of the box). During lowering the
    registry also records which shape classes each opcode was dispatched
    over (``classes_seen``) — the per-class registration benchmarks print.
    """

    def __init__(self, strict: bool = True) -> None:
        self._ops: List[Tuple[str, Optional[Callable]]] = []
        self._index: Dict[str, int] = {}
        self.strict = strict
        # opcode name -> set of (input class labels, output class labels)
        self.classes_seen: Dict[str, set] = {}
        # The ready-queue fast path's fixed kernel table: opcode name ->
        # elementwise shape-preserving branch fn the on-device lax.switch
        # may call. Eligibility requires a task's fn to BE the registered
        # branch (object identity), so the switch can never silently
        # diverge from what the host path would have executed.
        self._branch_fns: Dict[str, Callable] = {}

    def register(self, name: str, fn: Optional[Callable] = None) -> int:
        """Register ``name`` (idempotent). ``fn`` is the legacy uniform-path
        branch ``fn(x, y, z) -> out``; the arena path executes each task
        group's own wrapper-resolved callable and ignores it.

        Re-registering a known name upgrades an fn-less entry with the
        supplied branch fn; supplying a *different* fn for a name that
        already has one is a conflict and raises."""
        idx = self._index.get(name)
        if idx is not None:
            stored = self._ops[idx][1]
            if fn is not None:
                if stored is None:
                    self._ops[idx] = (name, fn)
                elif stored is not fn:
                    raise ValueError(
                        f"opcode {name!r} already registered with a different "
                        "branch fn; device opcodes are fixed per registry"
                    )
            return idx
        idx = len(self._ops)
        self._ops.append((name, fn))
        self._index[name] = idx
        return idx

    def opcode(self, name: str) -> int:
        idx = self._index.get(name)
        if idx is None:
            if not self.strict:
                return self.register(name)
            raise KeyError(
                f"opcode {name!r} is not in the device registry "
                f"(registered: {sorted(self._index) or 'none'}); register it "
                "or build the runner with an auto-registering registry"
            )
        return idx

    def note_classes(self, name: str, in_labels: Tuple[str, ...],
                     out_labels: Tuple[str, ...]) -> None:
        self.classes_seen.setdefault(name, set()).add((in_labels, out_labels))

    def register_switch_branch(self, name: str, fn: Callable) -> int:
        """Admit ``fn`` to the ready-queue fast path's fixed kernel table
        (and register the opcode name). Branches must be elementwise and
        row-shape-preserving — the Pallas loop stores each result over the
        task's output row. Re-registering the same fn is idempotent; a
        different fn for a known name is a conflict (the HW table is
        burned in)."""
        stored = self._branch_fns.get(name)
        if stored is not None and stored is not fn:
            raise ValueError(
                f"switch branch {name!r} already registered with a different "
                "fn; the device switch table is fixed per registry")
        self._branch_fns[name] = fn
        return self.register(name)

    def switch_branch(self, name: str) -> Optional[Callable]:
        """The registered fast-path branch fn for ``name`` (None if the
        opcode is interpreter-only)."""
        return self._branch_fns.get(name)

    @property
    def branches(self) -> List[Callable]:
        """Legacy uniform-path branch table (registration order). Opcode
        ints index this list inside ``lax.switch``, so every registered
        name must carry a branch fn to use the uniform interpreter."""
        missing = [n for n, fn in self._ops if fn is None]
        if missing:
            raise ValueError(
                "legacy uniform path needs an fn(x, y, z) branch for every "
                f"registered opcode; missing: {missing} (real kernels are "
                "registered fn-less — run them through the arena path)"
            )
        return [fn for _, fn in self._ops]

    def __contains__(self, name: str) -> bool:
        return name in self._index

    def __len__(self) -> int:
        return len(self._ops)


# ---------------------------------------------------------------------------
# Planning: run the windowed scheduler symbolically (no execution)
# ---------------------------------------------------------------------------

def plan_waves(tasks: Sequence[Task], window_size: int = 32,
               return_window: bool = False):
    """Run the windowed scheduler symbolically to obtain the wave plan.

    Planning cost rides the window's interval scoreboard: each insertion
    probes only its own segments' intervals, so planning at window
    128-512 costs barely more per task than at 32 (the seed's pairwise
    scan made large planning windows quadratic-feeling — see
    ``benchmarks/bench_window_size.py``).

    With ``return_window=True`` also returns the planning
    :class:`SchedulingWindow`, whose stats (dep checks, scoreboard
    probes, occupancy) are the real numbers behind the plan — the runner
    reports them instead of a fresh all-zero window.
    """
    window = SchedulingWindow(window_size)
    window.submit_all(tasks)
    waves: List[List[Task]] = []
    while not window.drained():
        ready = window.ready_tasks()
        if not ready:
            raise RuntimeError("stall while planning waves")
        for t in ready:
            window.mark_executing(t)
        waves.append(ready)
        window.retire_many(ready)
    return (waves, window) if return_window else waves


def plan_frontier(
    tasks: Sequence[Task], window_size: int = 32, max_group: Optional[int] = None,
    return_window: bool = False,
):
    """Frontier-plan mode: one homogeneous group per device step.

    Wave planning retires an entire front per step, so every step is
    padded to the *widest wave* and a slow-to-unblock kernel stretches the
    whole table. The frontier plan instead retires one homogeneous group at
    a time, re-collecting the READY set between groups — newly unblocked
    kernels join the very next step rather than waiting out the front.
    Steps are narrower but denser (higher active-slot fraction).
    """
    from .executors import group_by_signature

    window = SchedulingWindow(window_size)
    window.submit_all(tasks)
    groups: List[List[Task]] = []
    while not window.drained():
        ready = window.ready_tasks()
        if not ready:
            raise RuntimeError("stall while planning frontier groups")
        group = group_by_signature(ready)[0]
        if max_group is not None:
            group = group[:max_group]
        for t in group:
            window.mark_executing(t)
        window.retire_many(group)
        groups.append(group)
    return (groups, window) if return_window else groups


def plan_active_fraction(plan: Sequence[Sequence[Task]]) -> float:
    """Fraction of (step, slot) table cells holding a real kernel — the
    padding-waste metric the frontier plan improves."""
    if not plan:
        return 1.0
    max_w = max(len(step) for step in plan)
    return sum(len(step) for step in plan) / (len(plan) * max_w)


# ---------------------------------------------------------------------------
# Legacy lowering: one uniform (D,) shape class, arity <= 3
# ---------------------------------------------------------------------------

def compile_wave_plan(
    waves: Sequence[Sequence[Task]],
    registry: DeviceOpRegistry,
    buffer_index: Dict[str, int],
    n_rows: int,
) -> Dict[str, np.ndarray]:
    """Lower a wave schedule to dense dispatch tables (the 'SRAM' image).

    Legacy single-class path: every operand indexes one uniform slab and
    arity is capped at ``MAX_ARITY``. Over-arity tasks are an error here —
    the arena path (`lower_plan`) is the one without the limit.
    """
    n_waves = len(waves)
    max_w = max((len(w) for w in waves), default=1)
    dummy = n_rows  # slab has one extra scratch row
    opc = np.zeros((n_waves, max_w), dtype=np.int32)
    ins = np.full((n_waves, max_w, MAX_ARITY), dummy, dtype=np.int32)
    outs = np.full((n_waves, max_w), dummy, dtype=np.int32)
    active = np.zeros((n_waves, max_w), dtype=bool)
    for wi, wave in enumerate(waves):
        for si, task in enumerate(wave):
            if len(task.inputs) > MAX_ARITY:
                raise ValueError(
                    f"task {task.opcode}#{task.tid} has {len(task.inputs)} "
                    f"operands but the legacy uniform-slab path supports at "
                    f"most {MAX_ARITY}; use the arena path "
                    "(DeviceWindowRunner.execute) for variable arity"
                )
            if len(task.outputs) != 1:
                raise ValueError(
                    f"task {task.opcode}#{task.tid} has {len(task.outputs)} "
                    "outputs but the legacy uniform-slab path supports "
                    "exactly one; use the arena path "
                    "(DeviceWindowRunner.execute) for multi-output tasks"
                )
            opc[wi, si] = registry.opcode(task.opcode)
            for ai, op in enumerate(task.inputs):
                ins[wi, si, ai] = buffer_index[op.buffer.name if hasattr(op, "buffer") else op.name]
            outs[wi, si] = buffer_index[
                task.outputs[0].buffer.name if hasattr(task.outputs[0], "buffer") else task.outputs[0].name
            ]
            active[wi, si] = True
    return {"opcode": opc, "ins": ins, "outs": outs, "active": active}


# ---------------------------------------------------------------------------
# Arena lowering: per-class tables, variable arity, multi-output, views
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _OperandSpec:
    """Static half of one operand column (shared by the whole group)."""

    class_id: int
    true_shape: Tuple[int, ...]
    is_view: bool
    view_rows: int  # leading-axis rows covered when is_view


@dataclasses.dataclass(frozen=True)
class _StepSpec:
    """Static half of one device step: what gets compiled."""

    opcode: int
    width: int
    inputs: Tuple[_OperandSpec, ...]
    outputs: Tuple[_OperandSpec, ...]
    signature: Tuple  # group Task.signature — compile-cache identity


@dataclasses.dataclass
class DeviceStep:
    """One lowered step: one homogeneous task group, dense row tables.

    ``in_rows``/``out_rows`` are ``[n_operands, width]`` int32 slab row
    ids; ``*_starts`` carry the leading-axis offset for view operands
    (zero otherwise). The spec (opcode, width, shape classes) is static —
    identical specs across streams reuse one compiled program.
    """

    spec: _StepSpec
    fn: Callable
    in_rows: np.ndarray
    in_starts: np.ndarray
    out_rows: np.ndarray
    out_starts: np.ndarray
    tids: Tuple[int, ...]

    def tables(self) -> Dict[str, np.ndarray]:
        return {
            "in_rows": self.in_rows, "in_starts": self.in_starts,
            "out_rows": self.out_rows, "out_starts": self.out_starts,
        }


def _operand_spec(arena: SlabArena, op) -> Tuple[_OperandSpec, int, int]:
    """Returns (static spec, row, start) for one operand occurrence."""
    addr = arena.address(op)
    return (
        _OperandSpec(
            class_id=addr.class_id,
            true_shape=tuple(operand_shape(op)),
            is_view=addr.is_view,
            view_rows=addr.row_count if addr.is_view else 0,
        ),
        addr.row,
        addr.row_start,
    )


def _lowering_groups(wave: Sequence[Task], arena: SlabArena) -> List[List[Task]]:
    """Partition one plan step into arena-homogeneous groups, oldest-first.

    ``Task.signature`` alone is NOT enough here: it encodes operand value
    shapes, so a full ``(2, 4)`` buffer and a 2-row view of an ``(8, 4)``
    buffer are signature-equal (host executors batch them fine — they are
    value-based) yet need different gather/scatter code. The grouping key
    therefore also carries each operand's static arena addressing
    (class id, view-ness, view extent)."""

    def opkey(op):
        addr = arena.address(op)
        return (addr.class_id, addr.is_view, addr.row_count)

    groups: Dict[Tuple, List[Task]] = {}
    order: List[Tuple] = []
    for t in wave:
        key = (
            t.signature,
            tuple(opkey(o) for o in t.inputs),
            tuple(opkey(o) for o in t.outputs),
        )
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(t)
    return [groups[k] for k in order]


def lower_plan(
    plan: Sequence[Sequence[Task]],
    registry: DeviceOpRegistry,
    arena: SlabArena,
) -> List[DeviceStep]:
    """Lower a wave/frontier plan to arena-addressed device steps.

    Shared by both plan modes: each plan step (a wave, or an already
    homogeneous frontier group) is partitioned into arena-homogeneous
    groups (`_lowering_groups` — signature plus static arena addressing;
    tasks within a plan step are independent by construction, so sub-step
    order is free) and each group becomes one :class:`DeviceStep` with
    static (opcode, arity, shape classes) and dense per-operand row
    tables.
    """
    steps: List[DeviceStep] = []
    for wave in plan:
        for group in _lowering_groups(wave, arena):
            head = group[0]
            opcode = registry.opcode(head.opcode)
            n_in, n_out = len(head.inputs), len(head.outputs)
            width = len(group)
            in_specs: List[_OperandSpec] = []
            out_specs: List[_OperandSpec] = []
            in_rows = np.zeros((n_in, width), np.int32)
            in_starts = np.zeros((n_in, width), np.int32)
            out_rows = np.zeros((n_out, width), np.int32)
            out_starts = np.zeros((n_out, width), np.int32)
            for gi, task in enumerate(group):
                for i, op in enumerate(task.inputs):
                    spec, row, start = _operand_spec(arena, op)
                    in_rows[i, gi], in_starts[i, gi] = row, start
                    if gi == 0:
                        in_specs.append(spec)
                for o, op in enumerate(task.outputs):
                    spec, row, start = _operand_spec(arena, op)
                    out_rows[o, gi], out_starts[o, gi] = row, start
                    if gi == 0:
                        out_specs.append(spec)
            labels = tuple(arena.classes[s.class_id].label for s in in_specs)
            out_labels = tuple(arena.classes[s.class_id].label for s in out_specs)
            registry.note_classes(head.opcode, labels, out_labels)
            steps.append(
                DeviceStep(
                    spec=_StepSpec(opcode, width, tuple(in_specs),
                                   tuple(out_specs), head.signature),
                    fn=head.fn,
                    in_rows=in_rows, in_starts=in_starts,
                    out_rows=out_rows, out_starts=out_starts,
                    tids=tuple(t.tid for t in group),
                )
            )
    return steps


def _gather_operand(slabs, spec: _OperandSpec, rows, starts, width: int):
    """Gather one operand column: ``[width, *true_shape]`` (or unbatched
    when width == 1)."""
    slab = slabs[spec.class_id]
    if spec.is_view:
        rest = tuple(slab.shape[2:])  # padded row shape beyond the view axis
        zeros = (0,) * len(rest)

        def one(row, start):
            return jax.lax.dynamic_slice(
                slab[row], (start,) + zeros, (spec.view_rows,) + rest
            )

        vals = jax.vmap(one)(rows, starts) if width > 1 else one(rows[0], starts[0])
    else:
        vals = slab[rows] if width > 1 else slab[rows[0]]
    trim = tuple(slice(0, s) for s in spec.true_shape)
    if width > 1:
        trim = (slice(None),) + trim
    return vals[trim]


def _pad_value(val, target_shape: Tuple[int, ...]):
    if tuple(val.shape) == tuple(target_shape):
        return val
    pads = [(0, p - s) for s, p in zip(val.shape, target_shape)]
    return jnp.pad(val, pads)


def _scatter_operand(slabs, spec: _OperandSpec, rows, starts, width: int, val):
    """Scatter one output column back into its class slab."""
    slab = slabs[spec.class_id]
    padded_row = tuple(slab.shape[1:])
    if spec.is_view:
        # A view write updates a sub-interval of its parent's row. Within a
        # step two view writes may target the SAME parent row (disjoint
        # intervals — overlap would be a WAW hazard and land in different
        # steps), so the update must be sequential, not a vectorized
        # scatter that would drop all but one update to a duplicated row.
        target = (spec.view_rows,) + padded_row[1:]
        zeros = (0,) * (len(padded_row) - 1)
        for g in range(width):
            v = _pad_value(val[g] if width > 1 else val, target)
            row = rows[g]
            updated = jax.lax.dynamic_update_slice(
                slab[row], v.astype(slab.dtype), (starts[g],) + zeros
            )
            slab = slab.at[row].set(updated)
    else:
        if width > 1:
            v = jax.vmap(lambda x: _pad_value(x, padded_row))(val)
            slab = slab.at[rows].set(v.astype(slab.dtype))
        else:
            slab = slab.at[rows[0]].set(_pad_value(val, padded_row).astype(slab.dtype))
    out = list(slabs)
    out[spec.class_id] = slab
    return out


def _apply_step(slabs, spec: _StepSpec, fn: Callable, tables):
    ins = [
        _gather_operand(slabs, s, tables["in_rows"][i], tables["in_starts"][i],
                        spec.width)
        for i, s in enumerate(spec.inputs)
    ]
    out = jax.vmap(fn)(*ins) if spec.width > 1 else fn(*ins)
    outs = tuple(out) if isinstance(out, (tuple, list)) else (out,)
    if len(outs) != len(spec.outputs):
        raise ValueError(
            f"device step opcode {spec.opcode}: kernel returned {len(outs)} "
            f"values for {len(spec.outputs)} outputs"
        )
    for o, s in enumerate(spec.outputs):
        slabs = _scatter_operand(slabs, s, tables["out_rows"][o],
                                 tables["out_starts"][o], spec.width, outs[o])
    return slabs


def _build_program(
    steps: Sequence[DeviceStep],
) -> Tuple[Callable, List[Tuple[_StepSpec, Callable, int]]]:
    """Returns (jitted program, run segmentation). The program executes
    every lowered step; the segmentation tells `_run_tables` how to stack
    the per-step tables the program expects.

    Runs of consecutive steps with an identical static spec (the recurring
    structure of sim streams) collapse into a single ``lax.scan`` over
    their stacked row tables, bounding trace size by the number of
    *distinct* step specs in a run-length sense rather than total steps.
    """
    runs: List[Tuple[_StepSpec, Callable, int]] = []  # (spec, fn, run length)
    for st in steps:
        if runs and runs[-1][0] == st.spec:
            spec, fn, n = runs[-1]
            runs[-1] = (spec, fn, n + 1)
        else:
            runs.append((st.spec, st.fn, 1))

    def run_program(slabs, run_tables):
        slabs = list(slabs)
        for (spec, fn, length), tables in zip(runs, run_tables):
            if length == 1:
                slabs = _apply_step(slabs, spec, fn, tables)
            else:
                def body(carry, tbl, _spec=spec, _fn=fn):
                    return tuple(_apply_step(list(carry), _spec, _fn, tbl)), None

                carry, _ = jax.lax.scan(body, tuple(slabs), tables)
                slabs = list(carry)
        return tuple(slabs)

    return jax.jit(run_program), runs


def _run_tables(steps: Sequence[DeviceStep],
                runs: Sequence[Tuple[_StepSpec, Callable, int]]) -> List[Dict]:
    """Stack each run's per-step tables: [T, n_operands, width] for scans,
    plain [n_operands, width] for singleton runs."""
    tables: List[Dict] = []
    idx = 0
    for _, _, length in runs:
        chunk = steps[idx: idx + length]
        idx += length
        if length == 1:
            tables.append({k: jnp.asarray(v) for k, v in chunk[0].tables().items()})
        else:
            tables.append({
                k: jnp.asarray(np.stack([s.tables()[k] for s in chunk]))
                for k in chunk[0].tables()
            })
    return tables


# ---------------------------------------------------------------------------
# Ready-queue lowering: the whole dependency frontier in one dispatch
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EpochProgram:
    """One epoch lowered as a device-resident ready-queue program.

    Static halves (``specs``/``fns``/``opnames`` — what gets compiled) are
    separated from the device operands: per-spec dense address tables, the
    per-task ``(spec_id, spec_pos)`` dispatch map, the dependency arrays
    from :func:`~.scoreboard.dependency_arrays`, and the initial ring
    state. Order is decided *on device* by the queue; the tables only say
    where each task's operands live and who it wakes.
    """

    specs: Tuple[_StepSpec, ...]
    fns: Tuple[Callable, ...]
    opnames: Tuple[str, ...]
    spec_tables: List[Dict[str, np.ndarray]]  # per spec: [n_operands, count]
    spec_id: np.ndarray    # [n] int32: task position -> spec index
    spec_pos: np.ndarray   # [n] int32: task position -> column in its tables
    indeg: np.ndarray      # [n] int32 initial upstream counters
    dep_tbl: np.ndarray    # [n, m] int32 forward edges, sentinel n
    ring0: np.ndarray      # [n+1] int32 initially-ready positions, pad n
    tail0: int             # count of initially-ready tasks
    tids: Tuple[int, ...]

    @property
    def n_tasks(self) -> int:
        return len(self.tids)

    def payload(self) -> Dict[str, Any]:
        """The device-operand half, as jnp arrays (upload once, reuse
        across epochs via the plan cache)."""
        return {
            "tables": tuple(
                {k: jnp.asarray(v) for k, v in tbl.items()}
                for tbl in self.spec_tables),
            "spec_id": jnp.asarray(self.spec_id),
            "spec_pos": jnp.asarray(self.spec_pos),
            "dep_tbl": jnp.asarray(self.dep_tbl),
            "rem0": jnp.asarray(
                np.concatenate([self.indeg, np.zeros(1, np.int32)])),
            "ring0": jnp.asarray(self.ring0),
            "tail0": jnp.asarray([self.tail0], jnp.int32),
        }


def lower_epoch_program(tasks: Sequence[Task], registry: DeviceOpRegistry,
                        arena: SlabArena) -> EpochProgram:
    """Lower one epoch (tasks in program order) to a ready-queue program.

    Unlike :func:`lower_plan`, no host-side wave/frontier schedule exists:
    tasks group purely by structure (`_lowering_groups` over the whole
    epoch — signature + static arena addressing), each group contributing
    one spec and dense per-task address columns, and the exact dependency
    arrays ride along so the device can discover the execution order
    itself. Program order is topological (the window admits in program
    order), so every edge points forward and the queue never starves.
    """
    tasks = list(tasks)
    n = len(tasks)
    groups = _lowering_groups(tasks, arena)
    # Canonical group order: _lowering_groups returns first-occurrence
    # order, so two epochs over the SAME spec set but different arrival
    # interleavings would produce permuted `specs` tuples — distinct
    # program-cache keys and distinct jit traces for identical programs.
    # Spec order is semantically free here (the queue dispatches per task
    # through spec_id), so sort by structure and collapse the permutations.
    def _group_key(g):
        head = g[0]
        return (head.opcode, repr(head.signature),
                repr([(arena.address(o).class_id, arena.address(o).is_view,
                       arena.address(o).row_count)
                      for o in tuple(head.inputs) + tuple(head.outputs)]))

    groups.sort(key=_group_key)
    specs: List[_StepSpec] = []
    fns: List[Callable] = []
    opnames: List[str] = []
    spec_tables: List[Dict[str, np.ndarray]] = []
    spec_id = np.zeros(n, np.int32)
    spec_pos = np.zeros(n, np.int32)
    pos = {t.tid: i for i, t in enumerate(tasks)}
    for s, group in enumerate(groups):
        head = group[0]
        opcode = registry.opcode(head.opcode)
        n_in, n_out = len(head.inputs), len(head.outputs)
        count = len(group)
        in_specs: List[_OperandSpec] = []
        out_specs: List[_OperandSpec] = []
        tbl = {
            "in_rows": np.zeros((n_in, count), np.int32),
            "in_starts": np.zeros((n_in, count), np.int32),
            "out_rows": np.zeros((n_out, count), np.int32),
            "out_starts": np.zeros((n_out, count), np.int32),
        }
        for gi, task in enumerate(group):
            spec_id[pos[task.tid]] = s
            spec_pos[pos[task.tid]] = gi
            for i, op in enumerate(task.inputs):
                spec, row, start = _operand_spec(arena, op)
                tbl["in_rows"][i, gi], tbl["in_starts"][i, gi] = row, start
                if gi == 0:
                    in_specs.append(spec)
            for o, op in enumerate(task.outputs):
                spec, row, start = _operand_spec(arena, op)
                tbl["out_rows"][o, gi], tbl["out_starts"][o, gi] = row, start
                if gi == 0:
                    out_specs.append(spec)
        registry.note_classes(
            head.opcode,
            tuple(arena.classes[sp.class_id].label for sp in in_specs),
            tuple(arena.classes[sp.class_id].label for sp in out_specs))
        # width=1: the queue executes tasks one at a time, each slicing its
        # own column; the spec's signature keeps compile-cache identity.
        specs.append(_StepSpec(opcode, 1, tuple(in_specs), tuple(out_specs),
                               head.signature))
        fns.append(head.fn)
        opnames.append(head.opcode)
        spec_tables.append(tbl)

    indeg, dep_tbl = dependency_arrays(tasks)
    ready = np.flatnonzero(indeg == 0)
    ring0 = np.full(n + 1, n, np.int32)
    ring0[: len(ready)] = ready
    return EpochProgram(
        specs=tuple(specs), fns=tuple(fns), opnames=tuple(opnames),
        spec_tables=spec_tables, spec_id=spec_id, spec_pos=spec_pos,
        indeg=indeg, dep_tbl=dep_tbl, ring0=ring0, tail0=int(len(ready)),
        tids=tuple(t.tid for t in tasks),
    )


def _bucket(n: int, minimum: int = 8) -> int:
    """Next power-of-two bucket >= n (floored at ``minimum``)."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _padded_loop_payload(program: EpochProgram) -> Dict[str, Any]:
    """Bucket-pad the interpreter payload so jit signatures quantize.

    The loop interpreter's trace signature is the payload's SHAPES: task
    count ``n``, per-spec column counts, and dependency width ``m``. A
    live-fed window sees a near-continuous spread of all three, and every
    new combination silently retraces + XLA-compiles — which dominates
    wall time for small irregular kernels (exactly the regime the paper
    targets). Padding each dimension to a power-of-two bucket collapses
    that spread to O(log) signatures per spec set.

    Pad tasks are unreachable: their counters start at 1 and nothing
    points at them, so the ``head < tail`` loop drains exactly the real
    tasks and exits (this is why the Pallas path — whose ``fori_loop``
    pops exactly ``n`` tasks — keeps the exact payload instead). Dep-table
    sentinels are remapped from ``n`` to the padded count so they keep
    landing in the trash slot of ``remaining``/``ring``.
    """
    n = program.n_tasks
    n_p = _bucket(n)
    m = program.dep_tbl.shape[1]
    m_p = _bucket(max(m, 1), minimum=2)
    spec_id = np.zeros(n_p, np.int32)
    spec_id[:n] = program.spec_id
    spec_pos = np.zeros(n_p, np.int32)
    spec_pos[:n] = program.spec_pos
    dep_block = program.dep_tbl.astype(np.int32, copy=True)
    dep_block[dep_block == n] = n_p
    dep_tbl = np.full((n_p, m_p), n_p, np.int32)
    dep_tbl[:n, :m] = dep_block
    rem0 = np.ones(n_p + 1, np.int32)  # pad tasks never reach zero
    rem0[:n] = program.indeg
    rem0[n_p] = 0  # trash slot
    ring0 = np.full(n_p + 1, n_p, np.int32)
    ring0[: program.tail0] = program.ring0[: program.tail0]
    tables = []
    for tbl in program.spec_tables:
        count = tbl["in_rows"].shape[1] if tbl["in_rows"].size else \
            tbl["out_rows"].shape[1]
        c_p = _bucket(count)
        padded = {}
        for k, v in tbl.items():
            out = np.zeros((v.shape[0], c_p), np.int32)
            out[:, : v.shape[1]] = v
            padded[k] = jnp.asarray(out)
        tables.append(padded)
    return {
        "tables": tuple(tables),
        "spec_id": jnp.asarray(spec_id),
        "spec_pos": jnp.asarray(spec_pos),
        "dep_tbl": jnp.asarray(dep_tbl),
        "rem0": jnp.asarray(rem0),
        "ring0": jnp.asarray(ring0),
        "tail0": jnp.asarray([program.tail0], jnp.int32),
    }


def _build_loop_interpreter(specs: Sequence[_StepSpec],
                            fns: Sequence[Callable]) -> Callable:
    """The general ready-queue executor: a ``lax.while_loop`` over the
    slabs + counter/ring/flag state. Structurally the Pallas kernel
    (`kernels/ready_queue.py`) with none of its eligibility limits —
    views, mixed classes, multi-output and arbitrary arity all work, each
    task dispatching through ``lax.switch`` to its spec's column-sliced
    ``_apply_step``. One dispatch advances the whole frontier."""

    def run(slabs, payload):
        tables = payload["tables"]
        spec_id, spec_pos = payload["spec_id"], payload["spec_pos"]
        dep_tbl = payload["dep_tbl"]
        n = spec_id.shape[0]

        branches = []
        for s, (spec, fn) in enumerate(zip(specs, fns)):
            def br(operand, _spec=spec, _fn=fn, _s=s):
                slabs_, p = operand
                tbl = {k: jax.lax.dynamic_slice_in_dim(v, p, 1, axis=1)
                       for k, v in tables[_s].items()}
                return tuple(_apply_step(list(slabs_), _spec, _fn, tbl))
            branches.append(br)

        def cond(state):
            _, _, _, _, head, tail = state
            return head < tail

        def body(state):
            slabs_, remaining, ring, done, head, tail = state
            t = ring[head]
            slabs_ = jax.lax.switch(spec_id[t], branches,
                                    (slabs_, spec_pos[t]))
            done = done.at[t].set(1)
            deps = dep_tbl[t]  # [m], sentinel n lands in the trash slot
            remaining = remaining.at[deps].add(-1)
            newly = ((deps < n) & (remaining[deps] == 0)).astype(jnp.int32)
            offs = jnp.cumsum(newly) - newly
            slot = jnp.where(newly == 1, tail + offs, n)
            ring = ring.at[slot].set(deps)
            return (slabs_, remaining, ring, done, head + 1,
                    tail + jnp.sum(newly))

        state = (tuple(slabs), payload["rem0"], payload["ring0"],
                 jnp.zeros(n, jnp.int32), jnp.int32(0),
                 payload["tail0"][0])
        out = jax.lax.while_loop(cond, body, state)
        return out[0], out[3]

    return jax.jit(run)


def _loop_pallas_parts(program: EpochProgram, registry: DeviceOpRegistry,
                       arena: SlabArena):
    """Fast-path eligibility: ``(class_id, branches)`` when every spec fits
    the Pallas ready-queue kernel, else None. Requirements: one shape
    class of 4-byte, padding-free 1-D rows (a ``[rows, width]`` slab), no
    views, arity <= 3, exactly one output, and every fn IS its opcode's
    registered switch branch."""
    if not program.specs:
        return None
    cids = {sp.class_id for st in program.specs
            for sp in st.inputs + st.outputs}
    if len(cids) != 1:
        return None
    cid = cids.pop()
    padded = arena.classes[cid].padded_shape
    if len(padded) != 1 or np.dtype(arena.classes[cid].dtype).itemsize != 4:
        return None
    branches = []
    for spec, fn, name in zip(program.specs, program.fns, program.opnames):
        if len(spec.outputs) != 1 or len(spec.inputs) > 3:
            return None
        for sp in spec.inputs + spec.outputs:
            if sp.is_view or tuple(sp.true_shape) != tuple(padded):
                return None
        if registry.switch_branch(name) is not fn:
            return None
        arity = len(spec.inputs)
        branches.append(lambda x, y, z, _fn=fn, _k=arity:
                        _fn(*((x, y, z)[:_k])))
    return cid, tuple(branches)


def _select_loop_pallas(loop_pallas: Optional[bool], program: EpochProgram,
                        registry: DeviceOpRegistry, arena: SlabArena):
    """The "loop" executor choice for one epoch: ``(parts, over_cap)``.

    ``loop_pallas``: None = the Pallas kernel on TPU when the epoch is
    eligible (interpreter elsewhere), True = force it (interpret mode off
    TPU; still requires eligibility), False = the ``lax.while_loop``
    interpreter always. An eligible epoch too large for the kernel's
    SMEM/VMEM cap (``kernels/ready_queue.over_cap``) takes the
    interpreter and reports ``over_cap=True`` so callers can count it."""
    if loop_pallas is False or (loop_pallas is None
                                and jax.default_backend() != "tpu"):
        return None, False
    parts = _loop_pallas_parts(program, registry, arena)
    if parts is None:
        return None, False
    if _pallas_over_cap(program.n_tasks, program.dep_tbl.shape[1],
                        arena, parts[0]):
        return None, True
    return parts, False


def _pallas_over_cap(n_tasks: int, dep_width: int, arena: SlabArena,
                     class_id: int) -> bool:
    """Whether the class's slab (at the row capacity the next pack gives
    it) plus the epoch's tables exceed the Pallas kernel's cap."""
    from ..kernels.ready_queue import over_cap

    rows = row_capacity(len(arena.rows(class_id)))
    (width,) = arena.classes[class_id].padded_shape
    return over_cap(n_tasks, dep_width, rows, width) is not None


def _loop_task_table(program: EpochProgram) -> np.ndarray:
    """Flatten the per-spec tables into the Pallas kernel's ``[n, 5]``
    dispatch rows ``(branch, in0, in1, in2, out_row)``; unused input slots
    alias the task's own output row (always a valid slab index)."""
    n = program.n_tasks
    task_tbl = np.zeros((n, 5), np.int32)
    for i in range(n):
        s = int(program.spec_id[i])
        col = int(program.spec_pos[i])
        tbl = program.spec_tables[s]
        out_row = int(tbl["out_rows"][0, col])
        rows = [int(r) for r in tbl["in_rows"][:, col]]
        rows += [out_row] * (3 - len(rows))
        task_tbl[i] = [s] + rows + [out_row]
    return task_tbl


def _build_loop_pallas(class_id: int, branches: Tuple[Callable, ...],
                       interpret: bool) -> Callable:
    """Wrap the Pallas ready-queue kernel in the same (slabs, payload)
    calling convention as the interpreter, so the session's dispatch path
    is executor-agnostic."""
    from ..kernels.ready_queue import ready_queue_call

    def run(slabs, payload):
        slab, done = ready_queue_call(
            slabs[class_id], payload["task_tbl"], payload["dep_tbl"],
            payload["ring0"], payload["rem0"], payload["tail0"],
            branches=branches, interpret=interpret)
        out = list(slabs)
        out[class_id] = slab
        return tuple(out), done

    return run


class DeviceWindowRunner:
    """Compile once, then execute entire task streams in ONE dispatch.

    The arena path (``execute`` / ``run``) handles the real workloads:
    mixed shape classes, variable arity, multi-output tasks, row-view
    aliasing. It conforms to the ``make_scheduler`` contract — ``run``
    takes a task iterable and returns a :class:`SchedulerReport` whose
    window stats come from the planning pass (the dependency checks that
    actually happened), ``exec_stats.dispatches == 1`` per stream, and
    arena occupancy lands in ``report.arena_stats``.
    """

    def __init__(
        self,
        registry: Optional[DeviceOpRegistry] = None,
        window_size: int = 32,
        plan_mode: str = "wave",
        max_group: Optional[int] = None,
        pad_multiple: int = 8,
        loop_pallas: Optional[bool] = None,
    ):
        if plan_mode not in PLAN_MODES:
            raise ValueError(f"plan_mode must be one of {PLAN_MODES}, got {plan_mode!r}")
        self.registry = registry if registry is not None else DeviceOpRegistry(strict=False)
        self.window_size = window_size
        self.plan_mode = plan_mode
        self.max_group = max_group
        self.pad_multiple = pad_multiple
        # plan_mode="loop" executor selection: None = Pallas on TPU when a
        # stream is eligible (interpreter elsewhere), True = force the
        # Pallas kernel (interpret mode off-TPU; still requires
        # eligibility), False = lax.while_loop interpreter always.
        self.loop_pallas = loop_pallas
        self._compiled: Dict[Tuple, Tuple[Callable, Any]] = {}
        self._compiled_uniform: Dict[Tuple, Callable] = {}
        self.stats: Dict[str, Any] = {}

    def session(self) -> "DeviceSession":
        """Open a persistent :class:`DeviceSession` sharing this runner's
        opcode registry (each session owns its own arena — buffer rows bind
        to one session's slabs for its lifetime)."""
        return DeviceSession(window_size=self.window_size,
                             registry=self.registry,
                             plan_mode=self.plan_mode,
                             max_group=self.max_group,
                             pad_multiple=self.pad_multiple,
                             loop_pallas=self.loop_pallas)

    # -- shared planning ---------------------------------------------------
    def _plan(self, tasks: Sequence[Task]):
        if self.plan_mode == "frontier":
            return plan_frontier(tasks, self.window_size, self.max_group,
                                 return_window=True)
        return plan_waves(tasks, self.window_size, return_window=True)

    # -- arena path (the real workloads) -----------------------------------
    def run(self, stream: Iterable[Task]) -> SchedulerReport:
        """`make_scheduler` contract: task iterable in, report out."""
        return self.execute(list(stream))

    def execute(
        self,
        tasks: Sequence[Task],
        buffers: Optional[Sequence] = None,
    ) -> SchedulerReport:
        from .executors import ExecStats

        if self.plan_mode == "loop":
            return self._execute_loop(list(tasks), buffers)
        tasks = list(tasks)
        t0 = time.perf_counter()
        plan, window = self._plan(tasks)

        arena = SlabArena(pad_multiple=self.pad_multiple)
        if buffers is not None:
            for b in buffers:
                arena.add(b)
        arena.add_tasks(tasks)
        steps = lower_plan(plan, self.registry, arena)
        plan_time = time.perf_counter() - t0

        stats = ExecStats()
        key = (
            tuple(st.spec for st in steps),
            tuple((c.padded_shape, c.dtype, len(arena.rows(i)))
                  for i, c in enumerate(arena.classes)),
        )
        cached = self._compiled.get(key)
        if cached is None:
            cached = _build_program(steps)
            self._compiled[key] = cached
            stats.compiles += 1
        run_fn, runs = cached

        slabs = arena.pack()
        tables = _run_tables(steps, runs)
        t1 = time.perf_counter()
        out_slabs = run_fn(tuple(slabs), tables)
        jax.block_until_ready(out_slabs)
        exec_time = time.perf_counter() - t1
        written = [operand_base(op) for t in tasks for op in t.outputs]
        arena.unpack(out_slabs, only=None if buffers is not None else written)

        stats.dispatches = 1  # the whole stream was one launch
        stats.tasks_run = len(tasks)
        stats.wave_widths = [len(w) for w in plan]
        stats.exec_seconds = exec_time
        report = SchedulerReport(
            window, stats, plan_time + exec_time,
            [[t.tid for t in w] for w in plan],
        )
        report.plan_seconds = plan_time  # type: ignore[attr-defined]
        report.plan_mode = self.plan_mode  # type: ignore[attr-defined]
        report.plan_active_fraction = plan_active_fraction(plan)  # type: ignore[attr-defined]
        report.arena_stats = {  # type: ignore[attr-defined]
            "n_classes": arena.n_classes(),
            "total_waste_frac": round(arena.total_waste_frac(), 4),
            "per_class": arena.padding_waste(),
            "device_steps": len(steps),
        }
        return report

    def _execute_loop(
        self,
        tasks: List[Task],
        buffers: Optional[Sequence] = None,
    ) -> SchedulerReport:
        """plan_mode="loop": lower the whole stream as ONE ready-queue
        program — no host-side wave/frontier schedule at all; the device
        discovers execution order from the dependency arrays. The planning
        window still runs symbolically for its stats (the dependency
        checks are real either way), and the one host sync at the end
        asserts every completion flag — the queue provably drained."""
        t0 = time.perf_counter()
        _, window = plan_waves(tasks, self.window_size, return_window=True)

        arena = SlabArena(pad_multiple=self.pad_multiple)
        if buffers is not None:
            for b in buffers:
                arena.add(b)
        arena.add_tasks(tasks)
        program = lower_epoch_program(tasks, self.registry, arena)
        parts, over_cap = _select_loop_pallas(self.loop_pallas, program,
                                              self.registry, arena)
        plan_time = time.perf_counter() - t0

        stats = ExecStats()
        key = ("loop", program.specs, program.dep_tbl.shape[1],
               parts is not None,
               tuple((c.padded_shape, c.dtype, len(arena.rows(i)))
                     for i, c in enumerate(arena.classes)))
        run_fn = self._compiled.get(key)
        if run_fn is None:
            if parts is not None:
                run_fn = _build_loop_pallas(
                    parts[0], parts[1],
                    interpret=jax.default_backend() != "tpu")
            else:
                run_fn = _build_loop_interpreter(program.specs, program.fns)
            self._compiled[key] = run_fn
            stats.compiles += 1
        payload = program.payload()
        if parts is not None:
            payload["task_tbl"] = jnp.asarray(_loop_task_table(program))

        slabs = arena.pack()
        t1 = time.perf_counter()
        out_slabs, done = run_fn(tuple(slabs), payload)
        jax.block_until_ready(out_slabs)
        exec_time = time.perf_counter() - t1
        done_host = np.asarray(done)
        if not bool(done_host.all()):
            missing = [program.tids[i]
                       for i in np.flatnonzero(done_host == 0)]
            raise RuntimeError(
                f"ready-queue epoch stalled: tasks {missing} never became "
                "ready (dependency arrays disagree with program order)")
        written = [operand_base(op) for t in tasks for op in t.outputs]
        arena.unpack(out_slabs, only=None if buffers is not None else written)

        stats.dispatches = 1
        stats.tasks_run = len(tasks)
        stats.wave_widths = [len(tasks)]
        stats.exec_seconds = exec_time
        report = SchedulerReport(
            window, stats, plan_time + exec_time,
            [[t.tid for t in tasks]],
        )
        report.plan_seconds = plan_time  # type: ignore[attr-defined]
        report.plan_mode = self.plan_mode  # type: ignore[attr-defined]
        # Dense by construction: every table column holds a real task.
        report.plan_active_fraction = 1.0  # type: ignore[attr-defined]
        report.loop_executor = (  # type: ignore[attr-defined]
            "pallas" if parts is not None else "interpreter")
        report.loop_pallas_over_cap = over_cap  # type: ignore[attr-defined]
        report.arena_stats = {  # type: ignore[attr-defined]
            "n_classes": arena.n_classes(),
            "total_waste_frac": round(arena.total_waste_frac(), 4),
            "per_class": arena.padding_waste(),
            "device_steps": len(program.specs),
        }
        return report

    # -- legacy uniform path (seed behaviour, kept for the toy universe) ---
    def _uniform_interpreter(self):
        branches = self.registry.branches

        def step(slab, wave):
            # slab: [rows+1, D]; wave tables: opcode [S], ins [S,3], outs [S], active [S]
            def slot(opcode, in_ids, out_id, act):
                x = slab[in_ids[0]]
                y = slab[in_ids[1]]
                z = slab[in_ids[2]]
                res = jax.lax.switch(opcode, branches, x, y, z)
                return jnp.where(act, res, slab[out_id]), out_id

            results, out_ids = jax.vmap(slot)(
                wave["opcode"], wave["ins"], wave["outs"], wave["active"]
            )
            slab = slab.at[out_ids].set(results)
            return slab, None

        def run(slab, plan):
            slab, _ = jax.lax.scan(step, slab, plan)
            return slab

        return run

    def execute_uniform(
        self,
        tasks: Sequence[Task],
        buffers: Sequence,  # core.buffers.Buffer, uniform padded shape (D,)
    ) -> SchedulerReport:
        """The seed's single-shape-class interpreter (lax.switch over
        registry branches, arity <= 3, single output). Kept as the legacy
        reference; `execute` is the general path."""
        from .executors import ExecStats

        t0 = time.perf_counter()
        plan, window = self._plan(tasks)
        plan_time = time.perf_counter() - t0

        buffer_index = {b.name: i for i, b in enumerate(buffers)}
        n_rows = len(buffers)
        tables = compile_wave_plan(plan, self.registry, buffer_index, n_rows)

        d = int(buffers[0].shape[-1])
        key = (tables["opcode"].shape, d, len(self.registry))
        run = self._compiled_uniform.get(key)
        if run is None:
            run = jax.jit(self._uniform_interpreter())
            self._compiled_uniform[key] = run
        slab = jnp.stack([jnp.asarray(b.value) for b in buffers]
                         + [jnp.zeros((d,), dtype=buffers[0].value.dtype)])
        dev_plan = {k: jnp.asarray(v) for k, v in tables.items()}
        t1 = time.perf_counter()
        slab = run(slab, dev_plan)
        slab.block_until_ready()
        exec_time = time.perf_counter() - t1
        for i, b in enumerate(buffers):
            b.value = slab[i]

        stats = ExecStats()
        stats.dispatches = 1
        stats.tasks_run = len(tasks)
        stats.wave_widths = [len(w) for w in plan]
        stats.exec_seconds = exec_time
        report = SchedulerReport(window, stats, plan_time + exec_time,
                                 [[t.tid for t in w] for w in plan])
        report.plan_seconds = plan_time  # type: ignore[attr-defined]
        report.plan_mode = self.plan_mode  # type: ignore[attr-defined]
        report.plan_active_fraction = plan_active_fraction(plan)  # type: ignore[attr-defined]
        return report


# ---------------------------------------------------------------------------
# Persistent device window: the live-session form of the ACS-HW analogue
# ---------------------------------------------------------------------------

def _device_lowerable(task: Task) -> bool:
    """True iff every operand can live in the slab arena: array-valued (or
    not-yet-produced) buffers whose values match their declared shapes.
    Opaque pytree values (e.g. serving KV-cache tuples) and raw byte views
    fall back to the host path inside the epoch."""
    for op in tuple(task.inputs) + tuple(task.outputs):
        if isinstance(op, BufferView) and op.row_start is None:
            return False
        base = operand_base(op)
        val = base.value
        if val is None:
            continue
        shape = getattr(val, "shape", None)
        if shape is None or getattr(val, "dtype", None) is None:
            return False
        if tuple(shape) != tuple(base.shape):
            return False
    return True


class DeviceSession(SchedulerSession):
    """Persistent device-resident window: the rolling, live-fed ACS-HW
    analogue (DESIGN §2 A3).

    Producers ``submit()`` tasks (or feed a ``TaskStream(sink=session)``)
    at any time; each ``poll``/``drive`` drains everything admitted so far
    as one **epoch**:

    1. the live window is planned symbolically (wave fronts or frontier
       groups, exactly like the per-stream runner) — cross-epoch RAW/WAR
       edges were already resolved at insertion by the window, and epoch
       ordering retires them;
    2. the epoch's slice is lowered against the **session-lifetime arena**:
       slabs stay device-resident across epochs (only rows for newly seen
       buffers are appended), and a **structure-keyed plan cache** maps a
       recurring (signatures × arena addresses) slice straight to its
       lowered tables and compiled program — re-lowering is skipped
       entirely, the common case for RL sim steps and decode chains;
    3. the slice executes in ONE dispatch; host values re-sync only at
       retire boundaries (an epoch whose tasks have listeners, completion
       callbacks, or tickets; an explicit ``flush``/``close``/``sync``) —
       ``host_syncs`` counts them.

    Tasks whose operands cannot live in the arena (opaque pytree values,
    raw byte views) execute host-side *within* the epoch, interleaved in
    plan order with slab re-sync at each device/host transition — so the
    session still accepts any workload the host sessions accept.

    Device residency is a CONTRACT with the producer: while the session is
    open, buffers it has packed must be written only *through submitted
    tasks* — a direct host-side write to ``buf.value`` between epochs is
    invisible to the slabs (the host sessions would honor it) and the
    stale row wins. Symmetrically, reading ``buf.value`` after a bare
    ``poll()`` (no callback/ticket on the task) may observe a pre-epoch
    value until the next retire-boundary sync; call ``sync()`` (or
    ``flush``/``close``) before trusting direct reads.

    ``plan_mode="loop"`` replaces the host-scheduled step table with the
    **device-resident ready-queue executor** (DESIGN §2 A3): the epoch's
    tasks lower to per-spec address tables plus exact dependency arrays
    (`lower_epoch_program`), and a single ``lax.while_loop`` dispatch (or
    the Pallas kernel in ``kernels/ready_queue.py`` when the stream is
    switch-branch eligible) pops tasks as their on-device counters hit
    zero — retirement wakes dependents without ANY host round-trip, and
    tasks only transitively ready at launch still run in that dispatch.

    Per-epoch stats land in ``epoch_log`` and the aggregate in
    ``session_stats()`` / ``report.session_stats``: epochs, device
    dispatches (``loop_dispatches`` for ready-queue ones), plan-cache
    hits/misses, host syncs (d2h/h2d split, per stream tag), padding
    waste.
    """

    def __init__(
        self,
        window_size: int = 32,
        registry: Optional[DeviceOpRegistry] = None,
        plan_mode: str = "wave",
        max_group: Optional[int] = None,
        pad_multiple: int = 8,
        compact_waste: float = 0.5,
        compact_min_rows: int = 8,
        plan_cache_limit: Optional[int] = 512,
        history_limit: Optional[int] = None,
        loop_pallas: Optional[bool] = None,
        device: Optional[Any] = None,
        pad_payloads: bool = False,
    ):
        if plan_mode not in PLAN_MODES:
            raise ValueError(
                f"plan_mode must be one of {PLAN_MODES}, got {plan_mode!r}")
        super().__init__(window_size, history_limit=history_limit)
        self.registry = registry if registry is not None else DeviceOpRegistry(strict=False)
        self.plan_mode = plan_mode
        self.max_group = max_group
        # Optional jax.Device pin: slabs are committed there before each
        # dispatch, so jit execution (and every uncommitted payload array)
        # follows — this is what gives MeshDeviceSession's shards their
        # own dispatch streams. None keeps JAX's default placement.
        self.device = device
        # "loop" executor selection (see DeviceWindowRunner): None = Pallas
        # on TPU when eligible, True = force (interpret mode off-TPU),
        # False = lax.while_loop interpreter always.
        self.loop_pallas = loop_pallas
        # Opt-in payload shape-bucketing (interpreter path only): pads
        # epoch size, dep width and per-spec counts to pow2 buckets so a
        # serving stream whose per-epoch task counts wander does not
        # recompile every epoch. OFF by default because a bucketed program
        # is a DIFFERENT XLA program than the exact one — same math, but
        # compiler fusion may round differently at the last ulp, so exact
        # payloads are required wherever bit-identity with the serial
        # baseline is asserted. Benchmarks enable it on every session of
        # an A/B pair (single and mesh alike), so ratios stay fair.
        self.pad_payloads = pad_payloads
        self.arena = SlabArena(pad_multiple=pad_multiple,
                               compact_waste=compact_waste,
                               compact_min_rows=compact_min_rows)
        self._slabs: Optional[List[Any]] = None
        # id(Buffer) -> Buffer whose freshest value lives device-side
        # (slab newer than host) / host-side (host newer than slab).
        self._device_dirty: Dict[int, Buffer] = {}
        self._host_dirty: Dict[int, Buffer] = {}
        # id(Buffer) -> stream tag to attribute the pending h2d refresh to
        # (mesh staged edges tag their destination half "mesh-transfer").
        self._host_dirty_tags: Dict[int, str] = {}
        # structure key (plan signatures x arena addresses) -> lowered
        # (run_fn, tables, n_steps, class_gens): the session-scope plan
        # cache. Entries carry the arena generation of every class they
        # address; a compaction moves rows, so entries touching a compacted
        # class are invalidated (eagerly at compaction, and belt-and-braces
        # on hit via the recorded generations). Insertion order doubles as
        # LRU order (hits reinsert), bounded by plan_cache_limit.
        self._plan_cache: Dict[Tuple, Tuple] = {}
        self.plan_cache_limit = plan_cache_limit
        self.plan_cache_evictions = 0
        self.plan_cache_invalidations = 0
        # static step-spec structure -> compiled program (shared across
        # plan-cache entries that differ only in row addressing).
        self._programs: Dict[Tuple, Tuple[Callable, Any]] = {}
        self.stats = ExecStats()
        # In-epoch host-fallback path: a plain serial executor whose stats
        # object IS this session's, so its per-task dispatch/compile/jit
        # bookkeeping lands in the one report without duplication.
        self._host_exec = SerialExecutor()
        self._host_exec.stats = self.stats
        # id(Buffer) -> this session's device copy of a read-only buffer
        # (see replicate()).
        self._replicas: Dict[int, Any] = {}
        self.epochs = 0
        self.device_dispatches = 0
        self.loop_dispatches = 0  # ready-queue dispatches (subset of device)
        # Pallas-eligible loop epochs over the kernel's SMEM/VMEM cap,
        # which ran on the lax.while_loop interpreter instead.
        self.loop_pallas_over_cap = 0
        self.host_task_dispatches = 0
        self.plan_cache_hits = 0
        self.plan_cache_misses = 0
        # Host<->device transition accounting (DESIGN §2 A3: the O(1)
        # claim is only honest if EVERY transition is counted, in both
        # directions): `host_syncs` totals d2h slab read-backs plus h2d
        # row refreshes forced by host-fallback writes; the split and a
        # per-stream-tag attribution ride along for the benchmarks.
        self.host_syncs = 0
        self.host_syncs_d2h = 0
        self.host_syncs_h2d = 0
        self.host_syncs_by_tag: Dict[str, int] = {}
        # Mesh d2d edge accounting: rows peer-copied out of / into this
        # session's slabs without a host round-trip, and device-dirty
        # claims dropped because another shard took write ownership.
        self.d2d_row_exports = 0
        self.d2d_row_imports = 0
        self.row_invalidations = 0
        # Overlapped-drain surface (mesh): launch() dispatches epochs with
        # retirement DEFERRED — each device segment parks here with its
        # output slabs as completion probes until poll_inflight() retires
        # it (FIFO, preserving program-order retirement).
        self._inflight: deque = deque()
        self._defer_retire = False
        self.epoch_log: Any = ([] if history_limit is None
                               else deque(maxlen=history_limit))

    # -- epoch planning ----------------------------------------------------
    def _plan_epoch(self) -> List[List[Task]]:
        """Drain the live window symbolically into this epoch's plan:
        wave fronts or one homogeneous frontier group per step. The window
        retires (and refills from the FIFO) during planning — execution
        follows, then retirement callbacks fire. The replanning is cheap
        by construction: upstream sets were resolved incrementally by the
        scoreboard at submit time, and each retire-and-refill here costs
        O(own segments + out-degree), not a window rescan — so epoch
        planning at window 256 does not melt the admission path.

        QoS threading (DESIGN §13): ``ready_tasks()`` is priority-
        bucketed, so each planning step's frontier opens with the most
        urgent READY kernels — frontier-mode epochs pick their leading
        signature group from the urgent end, wave-mode fronts list
        urgent work first. ``plan_mode="loop"`` epochs are unaffected:
        they drain via ``drain_program_order()`` (seq-sorted, priority-
        oblivious), keeping the §2-A3 loop lowering program-order-
        correct — on-device, the ready ring still discovers whatever
        concurrency exists regardless of class."""
        plan: List[List[Task]] = []
        while not self.window.idle():
            ready = self.window.ready_tasks()
            if not ready:
                raise RuntimeError(
                    "device session stall: no READY kernels but window non-empty")
            if self.plan_mode == "frontier":
                group = group_by_signature(ready)[0]
                if self.max_group is not None:
                    group = group[: self.max_group]
            else:
                group = ready
            for t in group:
                self.window.mark_executing(t)
            self.window.retire_many(group)
            plan.append(group)
        return plan

    # -- sync bookkeeping --------------------------------------------------
    @staticmethod
    def _tags_of(tasks: Iterable[Task]) -> Tuple[str, ...]:
        return tuple({getattr(t, "stream_tag", None) or "untagged"
                      for t in tasks})

    def _count_sync(self, direction: str, tags: Iterable[str]) -> None:
        self.host_syncs += 1
        if direction == "d2h":
            self.host_syncs_d2h += 1
        else:
            self.host_syncs_h2d += 1
        for tag in tags or ("untagged",):
            self.host_syncs_by_tag[tag] = self.host_syncs_by_tag.get(tag, 0) + 1

    def _sync_to_host(self, buffers: Iterable[Buffer],
                      tags: Iterable[str] = ()) -> None:
        """Write the given buffers' slab rows back to host values (ONE
        blocking sync, counted; ``tags`` attributes it to the stream tags
        that forced it)."""
        bufs = [b for b in buffers if id(b) in self._device_dirty]
        if not bufs or self._slabs is None:
            return
        jax.block_until_ready(self._slabs)
        self.arena.unpack(self._slabs, only=bufs)
        for b in bufs:
            del self._device_dirty[id(b)]
        self._count_sync("d2h", tuple(tags))

    def sync(self) -> None:
        """Force every device-resident value back to host buffers."""
        with self._lock:
            self._sync_to_host(list(self._device_dirty.values()),
                               tags=("sync",))

    def sync_buffers(self, buffers: Iterable[Buffer],
                     tags: Iterable[str] = ("transfer",)) -> None:
        """Sync just the given buffers' device values back to host (one
        counted d2h when any is device-dirty). The mesh session stages a
        cross-shard edge as: owner ``sync_buffers`` -> destination
        ``mark_host_dirty`` -> destination's next dispatch re-uploads."""
        with self._lock:
            self._sync_to_host(list(buffers), tags=tuple(tags))

    def mark_host_dirty(self, buf: Buffer, tag: Optional[str] = None) -> None:
        """Tell this session the buffer's HOST value is now authoritative
        (another shard produced it, or the producer rewrote it between
        epochs): drop any stale device-dirty claim and schedule a row
        refresh at the next dispatch. No-op for buffers this session's
        arena has never packed — their next pack reads host values anyway.
        ``tag`` attributes the eventual h2d refresh to the stream that
        forced it (the mesh staged path passes ``"mesh-transfer"`` so both
        halves of a staged edge land in the per-tag sync audit)."""
        with self._lock:
            self._device_dirty.pop(id(buf), None)
            if buf in self.arena:
                self._host_dirty[id(buf)] = buf
                if tag is not None:
                    self._host_dirty_tags[id(buf)] = tag

    # -- d2d row transfer (mesh ShardLink halves) ---------------------------
    def export_row(self, buf: Buffer) -> Optional[Any]:
        """The device-resident slab row holding ``buf``'s authoritative
        padded value, for a peer shard to import without a host hop — or
        ``None`` when this session holds no device-authoritative copy
        (host value current, row never materialized, or pending a host
        refresh), in which case the caller must take the host-staged
        path. The export is a lazy slice: it does NOT block on in-flight
        dispatches — the receiving ``.at[row].set`` stays async too."""
        with self._lock:
            if self._slabs is None or id(buf) not in self._device_dirty:
                return None
            addr = self.arena.addr_of(buf)
            if addr is None:
                return None
            cid, _row = addr
            try:
                row = self.arena.export_row(
                    self._slabs, buf,
                    expected_generation=self.arena.class_generation(cid))
            except RuntimeError:
                return None
            self.d2d_row_exports += 1
            return row

    def import_row(self, buf: Buffer, value: Any) -> bool:
        """Receive a peer shard's exported slab row directly into this
        session's slab (d2d edge): the row becomes device-authoritative
        here — exactly the state a local dispatch write leaves — so every
        downstream sync/observer path behaves identically. Returns False
        (caller falls back to host staging) when this session has no
        pinned device to commit the peer value onto."""
        with self._lock:
            if self.device is None:
                return False
            self.arena.add(buf)
            cid, _row = self.arena.addr_of(buf)
            # Materialize any not-yet-packed rows first (admission upload,
            # not a counted sync): a first-touch import needs its row
            # inside the packed watermark. Then pin, so the functional
            # .at[].set commits onto this shard's device.
            self._slabs = self.arena.pack_incremental(self._slabs,
                                                      device=self.device)
            self._slabs = [jax.device_put(s, self.device)
                           for s in self._slabs]
            self._slabs = self.arena.import_row(
                self._slabs, buf, value,
                expected_generation=self.arena.class_generation(cid))
            self._host_dirty.pop(id(buf), None)
            self._host_dirty_tags.pop(id(buf), None)
            self._device_dirty[id(buf)] = buf
            self.d2d_row_imports += 1
            return True

    def invalidate_row(self, buf: Buffer) -> bool:
        """Drop any authoritative claim this session holds on ``buf`` —
        the write-owner invalidation half of the mesh protocol: when
        another shard takes write ownership, every superseded copy must
        stop asserting its (now stale) value, or a later sync here would
        clobber the fresh one. The slab row keeps its bits; a future read
        on this shard re-stages through the link first."""
        with self._lock:
            had = self._device_dirty.pop(id(buf), None) is not None
            self._host_dirty.pop(id(buf), None)
            self._host_dirty_tags.pop(id(buf), None)
            if had:
                self.row_invalidations += 1
            return had

    # -- row lifecycle -------------------------------------------------------
    def release_buffer(self, buf: Buffer) -> bool:
        """Release a buffer the producer is done with: its arena row joins
        the class free-list for recycling and its dirty-tracking entries
        drop. The caller guarantees no pending or future task references
        the buffer (serving wires this to ``BufferPool.free`` via a free
        hook, which fires after the owning request retired). The device
        value is NOT synced back — a released buffer owes no host value."""
        with self._lock:
            self._device_dirty.pop(id(buf), None)
            self._host_dirty.pop(id(buf), None)
            self._host_dirty_tags.pop(id(buf), None)
            self._replicas.pop(id(buf), None)
            return self.arena.free(buf)

    def _maybe_compact(self) -> None:
        """Compact classes whose dead-row waste crossed the arena threshold
        (called with the lock held, between dispatches). Cached plans hold
        static row addresses, so every plan-cache entry addressing a
        compacted class is dropped — exactly those, never the full cache:
        entries over untouched classes stay valid and keep hitting."""
        cids = self.arena.needs_compaction()
        if not cids:
            return
        self._slabs, moved = self.arena.compact(self._slabs, cids)
        stale = [k for k, entry in self._plan_cache.items()
                 if any(cid in moved for cid, _ in entry[3])]
        for k in stale:
            del self._plan_cache[k]
        self.plan_cache_invalidations += len(stale)

    # Observers registered AFTER an unwatched epoch retired their task hit
    # the base class's fire-immediately paths — sync first, so a late
    # callback/ticket holder reads host values as fresh as an early one's.
    def _pre_observe_retired(self, task: Task) -> None:
        self._sync_to_host(list(self._device_dirty.values()),
                           tags=self._tags_of([task]))

    # -- device / host halves ----------------------------------------------
    def _structure_key(self, dev_plan: Sequence[Sequence[Task]]) -> Tuple:
        def opkey(op):
            a = self.arena.address(op)
            return (a.class_id, a.row, a.row_start, a.row_count)

        return tuple(
            tuple(
                (t.signature,
                 tuple(opkey(o) for o in t.inputs),
                 tuple(opkey(o) for o in t.outputs))
                for t in step
            )
            for step in dev_plan
        )

    def _execute_device(self, dev_plan: List[List[Task]]) -> None:
        self._maybe_compact()
        tasks = [t for step in dev_plan for t in step]
        self.arena.add_tasks(tasks)
        key = (self.plan_mode, self._structure_key(dev_plan))
        cached = self._plan_cache.get(key)
        if cached is not None and any(
                self.arena.class_generation(cid) != gen
                for cid, gen in cached[3]):
            # A compaction moved this entry's rows after it was built (the
            # eager sweep should have caught it — this is the safety net).
            del self._plan_cache[key]
            self.plan_cache_invalidations += 1
            cached = None
        if cached is None:
            steps = lower_plan(dev_plan, self.registry, self.arena)
            # Program cache keys on step structure alone: jit retraces by
            # itself when slab shapes grow, so keying on the arena layout
            # would only manufacture duplicate jit wrappers.
            spec_key = tuple(st.spec for st in steps)
            prog = self._programs.get(spec_key)
            if prog is None:
                prog = _build_program(steps)
                self._programs[spec_key] = prog
                self.stats.compiles += 1
            run_fn, runs = prog
            tables = _run_tables(steps, runs)
            class_ids = sorted({
                spec.class_id for st in steps
                for spec in st.spec.inputs + st.spec.outputs})
            gens = tuple(
                (cid, self.arena.class_generation(cid)) for cid in class_ids)
            cached = (run_fn, tables, len(steps), gens)
            self._plan_cache[key] = cached
            self.plan_cache_misses += 1
            if self.plan_cache_limit is not None and \
                    len(self._plan_cache) > self.plan_cache_limit:
                self._plan_cache.pop(next(iter(self._plan_cache)))
                self.plan_cache_evictions += 1
        else:
            # LRU touch: reinsertion moves the entry to the young end.
            self._plan_cache[key] = self._plan_cache.pop(key)
            self.plan_cache_hits += 1
        run_fn, tables, n_steps, _ = cached

        # Persistent slabs: append rows for newly seen buffers, refresh
        # rows whose host values changed since they were packed.
        self._refresh_slabs(tasks)

        out = run_fn(tuple(self._slabs), tables)
        self._slabs = list(out)
        self.device_dispatches += 1
        self.stats.dispatches += 1
        self.stats.tasks_run += len(tasks)
        for step in dev_plan:
            self.stats.wave_widths.append(len(step))
        for t in tasks:
            for op in t.outputs:
                b = operand_base(op)
                self._device_dirty[id(b)] = b
                self._host_dirty.pop(id(b), None)

    def _refresh_slabs(self, tasks: List[Task]) -> None:
        """Bring the slabs up to date before a device dispatch: append rows
        for newly seen buffers (admission upload — not a sync round-trip)
        and refresh rows whose host values changed since packing. The
        refresh IS a host->device transition (the opaque-operand fallback
        wrote those buffers host-side), so it counts toward host_syncs."""
        self._slabs = self.arena.pack_incremental(self._slabs,
                                                  device=self.device)
        stale = [b for b in self._host_dirty.values() if b in self.arena]
        if stale:
            self._slabs = self.arena.update_rows(self._slabs, stale)
            tags = set(self._tags_of(tasks))
            for b in stale:
                del self._host_dirty[id(b)]
                forced = self._host_dirty_tags.pop(id(b), None)
                if forced is not None:
                    tags.add(forced)
            self._count_sync("h2d", tuple(tags))
        if self.device is not None:
            # Commit to the pinned device (no-op for rows already there);
            # dispatch then executes on it regardless of JAX's default.
            self._slabs = [jax.device_put(s, self.device)
                           for s in self._slabs]

    def _execute_host_step(self, tasks: List[Task]) -> None:
        """In-epoch host fallback (opaque operands): per-task jit dispatch,
        reading fresh values back from the slabs first when a device step
        produced them. Retirement fires per task, so chained callbacks
        (serving decode harvests) observe each intermediate value exactly
        as they would under the host sessions."""
        need: Dict[int, Buffer] = {}
        for t in tasks:
            for op in tuple(t.inputs) + tuple(t.outputs):
                base = operand_base(op)
                if id(base) in self._device_dirty:
                    need[id(base)] = base
        if need:
            self._sync_to_host(need.values(), tags=self._tags_of(tasks))
        for task in tasks:
            self._host_exec.run_task(task, self._host_inputs(task))
            self.host_task_dispatches += 1
            for op in task.outputs:
                b = operand_base(op)
                self._host_dirty[id(b)] = b
                self._device_dirty.pop(id(b), None)
            self.waves.append([task.tid])
            self._note_retired(task)

    def _host_inputs(self, task: Task) -> Tuple[Any, ...]:
        """A host-path task's input values, committed to the pinned device
        so that its dispatch runs there and its outputs stay there:
        replicated buffers resolve to this session's own copy, every other
        value is moved (a no-op for values already on the device). With
        no pinned device the values pass through and the task runs where
        they live."""
        values = task.input_values()
        if self.device is None:
            return values
        return tuple(
            self._replicas[id(op)] if id(op) in self._replicas
            else jax.device_put(v, self.device)
            for op, v in zip(task.inputs, values))

    def replicate(self, buf: Buffer) -> None:
        """Keep a copy of read-only ``buf``'s value on the pinned device:
        host-path tasks read that copy instead of moving the value per
        dispatch. The caller guarantees no task writes ``buf`` while this
        session is open (serving weights)."""
        with self._lock:
            if self.device is not None:
                self._replicas[id(buf)] = jax.device_put(buf.value,
                                                         self.device)

    def _drain_epoch_ordered(self) -> List[Task]:
        """Drain the live window into program order (the ready-queue
        lowering needs a topological order) — see
        :meth:`SchedulingWindow.drain_program_order`."""
        return self.window.drain_program_order()

    def _execute_device_loop(self, tasks: List[Task]) -> None:
        """Dispatch one program-order run of device-lowerable tasks as a
        single ready-queue program: the device pops tasks as their
        counters hit zero — the host never decides a wake-up. Rides the
        same structure-keyed plan cache as the fixed-table path (payload
        arrays are cached device-side, so a recurring stream re-uploads
        nothing) and the same spec-keyed program cache."""
        self._maybe_compact()
        self.arena.add_tasks(tasks)
        key = ("loop", self._structure_key([tasks]))
        cached = self._plan_cache.get(key)
        if cached is not None and any(
                self.arena.class_generation(cid) != gen
                for cid, gen in cached[3]):
            del self._plan_cache[key]
            self.plan_cache_invalidations += 1
            cached = None
        if cached is not None and cached[4] is not None and \
                _pallas_over_cap(len(tasks), cached[1]["dep_tbl"].shape[1],
                                 self.arena, cached[4]):
            # The slab grew past the Pallas kernel's cap since this plan
            # was lowered: re-lower, which selects the interpreter.
            del self._plan_cache[key]
            cached = None
        if cached is None:
            program = lower_epoch_program(tasks, self.registry, self.arena)
            parts, over_cap = _select_loop_pallas(
                self.loop_pallas, program, self.registry, self.arena)
            self.loop_pallas_over_cap += int(over_cap)
            # Interpreter payloads are bucket-padded only when the session
            # opted in (shape quantization — see _padded_loop_payload);
            # the Pallas fori_loop pops exactly n tasks, so the fast path
            # always keeps the exact payload.
            if parts is None and self.pad_payloads:
                payload = _padded_loop_payload(program)
            else:
                payload = program.payload()
                if parts is not None:
                    payload["task_tbl"] = jnp.asarray(
                        _loop_task_table(program))
            spec_key = ("loop", program.specs,
                        payload["dep_tbl"].shape[1], parts is not None)
            prog = self._programs.get(spec_key)
            if prog is None:
                if parts is not None:
                    prog = _build_loop_pallas(
                        parts[0], parts[1],
                        interpret=jax.default_backend() != "tpu")
                else:
                    prog = _build_loop_interpreter(program.specs, program.fns)
                self._programs[spec_key] = prog
                self.stats.compiles += 1
            class_ids = sorted({
                sp.class_id for st in program.specs
                for sp in st.inputs + st.outputs})
            gens = tuple(
                (cid, self.arena.class_generation(cid)) for cid in class_ids)
            cached = (prog, payload, len(program.specs), gens,
                      parts[0] if parts is not None else None)
            self._plan_cache[key] = cached
            self.plan_cache_misses += 1
            if self.plan_cache_limit is not None and \
                    len(self._plan_cache) > self.plan_cache_limit:
                self._plan_cache.pop(next(iter(self._plan_cache)))
                self.plan_cache_evictions += 1
        else:
            self._plan_cache[key] = self._plan_cache.pop(key)
            self.plan_cache_hits += 1
        run_fn, payload = cached[:2]

        self._refresh_slabs(tasks)
        out, _done = run_fn(tuple(self._slabs), payload)
        self._slabs = list(out)
        self.device_dispatches += 1
        self.loop_dispatches += 1
        self.stats.dispatches += 1
        self.stats.tasks_run += len(tasks)
        self.stats.wave_widths.append(len(tasks))
        for t in tasks:
            for op in t.outputs:
                b = operand_base(op)
                self._device_dirty[id(b)] = b
                self._host_dirty.pop(id(b), None)

    def _run_epoch_loop(self) -> None:
        """The plan_mode="loop" epoch: split the program-order drain into
        maximal contiguous device-lowerable runs — each run is ONE
        ready-queue dispatch (order decided on device); opaque-operand
        runs interleave on the host path in between. Program order is
        topological, so run ordering preserves every cross-run edge."""
        order = self._drain_epoch_ordered()
        syncs_before = self.host_syncs
        hits_before = self.plan_cache_hits
        n_device_dispatches = 0
        n_host_tasks = 0
        for lowerable, grp in itertools.groupby(order, key=_device_lowerable):
            run = list(grp)
            if lowerable:
                self._execute_device_loop(run)
                n_device_dispatches += 1
                self._retire_device_segment([run])
            else:
                n_host_tasks += len(run)
                self._execute_host_step(run)
        self.epochs += 1
        self.epoch_log.append({
            "epoch": self.epochs,
            "tasks": len(order),
            "plan_steps": n_device_dispatches + n_host_tasks,
            "device_dispatches": n_device_dispatches,
            "host_tasks": n_host_tasks,
            "plan_cache_hits": self.plan_cache_hits - hits_before,
            "host_syncs": self.host_syncs - syncs_before,
        })

    # -- the epoch ----------------------------------------------------------
    def _pump(self) -> bool:
        # Segments a prior launch() left in flight retire first (blocking:
        # _pump must make progress) — flush/close after a launch drains
        # cleanly instead of stalling on a window that looks idle.
        progressed = False
        if self._inflight:
            progressed = self._drain_inflight(block=True) > 0
        if self.window.idle():
            return progressed
        if self.plan_mode == "loop":
            self._run_epoch_loop()
        else:
            self._run_epoch()
        return True

    # -- overlapped drain (mesh pump) ---------------------------------------
    def launch(self) -> bool:
        """Dispatch everything admitted so far WITHOUT retiring device
        segments: each device dispatch is enqueued async and parked on the
        in-flight queue; its retirement — observer sync, callbacks,
        outstanding accounting — happens at :meth:`poll_inflight`. This is
        the mesh session's overlapped-drain hook: launching every involved
        shard back-to-back puts independent shards' epochs in flight
        concurrently before anyone blocks. Host-fallback tasks still
        execute and retire inline (their operand syncs block anyway).
        Returns True when anything is in flight or was dispatched."""
        with self._lock:
            if self.window.idle():
                return bool(self._inflight)
            self._defer_retire = True
            try:
                if self.plan_mode == "loop":
                    self._run_epoch_loop()
                else:
                    self._run_epoch()
            finally:
                self._defer_retire = False
            return True

    @property
    def inflight_segments(self) -> int:
        with self._lock:
            return len(self._inflight)

    def poll_inflight(self, block: bool = False) -> int:
        """Retire in-flight device segments whose dispatches have landed,
        oldest-first (program-order retirement). Non-blocking by default:
        stops at the first segment whose output slabs are not ready.
        ``block=True`` forces the oldest segment to completion first.
        Returns the number of tasks retired."""
        with self._lock:
            return self._drain_inflight(block=block)

    def _drain_inflight(self, block: bool) -> int:
        retired = 0
        while self._inflight:
            dev_plan, probes = self._inflight[0]
            if not block and not all(p.is_ready() for p in probes):
                break
            if block:
                jax.block_until_ready(list(probes))
            self._inflight.popleft()
            self._retire_device_segment(dev_plan)
            retired += sum(len(step) for step in dev_plan)
            block = False  # only force the oldest; the rest must be ready
        return retired

    def _retire_device_segment(self, dev_plan: List[List[Task]]) -> None:
        """Retire a just-dispatched device segment. Retirement observers —
        listeners, per-task callbacks, ticket holders — read host values,
        so a watched segment syncs the slabs back first (one blocking sync
        — the retire boundary); observation granularity is the segment,
        since intermediate slab states inside its single dispatch are
        never materialized. Under a deferred launch the segment parks on
        the in-flight queue instead, with the dispatch's output slabs as
        completion probes; poll_inflight re-enters here to finish the
        job."""
        if self._defer_retire:
            self._inflight.append((dev_plan, tuple(self._slabs or ())))
            return
        watched = bool(self._listeners) or any(
            t.tid in self._watchers or t.tid in self._tickets
            for step in dev_plan for t in step)
        if watched:
            self._sync_to_host(
                list(self._device_dirty.values()),
                tags=self._tags_of(t for step in dev_plan for t in step))
        for step in dev_plan:
            self.waves.append([t.tid for t in step])
            for t in step:
                self._note_retired(t)

    def _run_epoch(self) -> None:
        plan = self._plan_epoch()
        syncs_before = self.host_syncs
        hits_before = self.plan_cache_hits
        n_device_dispatches = 0
        n_host_tasks = 0
        # Walk the plan in order, batching maximal runs of device-lowerable
        # steps into single dispatches; tasks within one plan step are
        # independent, so splitting a step between the device and host
        # halves preserves every cross-step dependency (plan order).
        pending: List[List[Task]] = []
        for step in plan:
            dev = [t for t in step if _device_lowerable(t)]
            host = [t for t in step if not _device_lowerable(t)]
            if dev:
                pending.append(dev)
            if host:
                if pending:
                    self._execute_device(pending)
                    n_device_dispatches += 1
                    self._retire_device_segment(pending)
                    pending = []
                n_host_tasks += len(host)
                self._execute_host_step(host)
        if pending:
            self._execute_device(pending)
            n_device_dispatches += 1
            self._retire_device_segment(pending)

        self.epochs += 1
        self.epoch_log.append({
            "epoch": self.epochs,
            "tasks": sum(len(step) for step in plan),
            "plan_steps": len(plan),
            "device_dispatches": n_device_dispatches,
            "host_tasks": n_host_tasks,
            "plan_cache_hits": self.plan_cache_hits - hits_before,
            "host_syncs": self.host_syncs - syncs_before,
        })

    # -- lifecycle ----------------------------------------------------------
    def flush(self) -> None:
        """Drain everything submitted so far, then sync device-resident
        values back to host buffers (the observable retire boundary)."""
        super().flush()
        self.sync()

    def session_stats(self) -> Dict[str, Any]:
        """Aggregate session counters (the per-epoch detail is in
        ``epoch_log``)."""
        with self._lock:
            return {
                "plan_mode": self.plan_mode,
                "epochs": self.epochs,
                "device_dispatches": self.device_dispatches,
                "loop_dispatches": self.loop_dispatches,
                "loop_pallas_over_cap": self.loop_pallas_over_cap,
                "host_task_dispatches": self.host_task_dispatches,
                "plan_cache_hits": self.plan_cache_hits,
                "plan_cache_misses": self.plan_cache_misses,
                "plan_cache_entries": len(self._plan_cache),
                "plan_cache_evictions": self.plan_cache_evictions,
                "plan_cache_invalidations": self.plan_cache_invalidations,
                "compiled_programs": len(self._programs),
                "host_syncs": self.host_syncs,
                "host_syncs_d2h": self.host_syncs_d2h,
                "host_syncs_h2d": self.host_syncs_h2d,
                "host_syncs_by_tag": dict(self.host_syncs_by_tag),
                "d2d_row_exports": self.d2d_row_exports,
                "d2d_row_imports": self.d2d_row_imports,
                "row_invalidations": self.row_invalidations,
                "n_classes": self.arena.n_classes(),
                "padding_waste_frac": round(self.arena.total_waste_frac(), 4),
                # row lifecycle (DESIGN §2 A3 gap (2))
                "slab_bytes": self.arena.slab_bytes(),
                "arena_generation": self.arena.generation,
                "arena_live_rows": self.arena.live_rows(),
                "arena_free_rows": self.arena.free_rows(),
                "arena_recycled_rows": self.arena.recycled_rows,
                "arena_compactions": self.arena.compactions,
                # dependency-engine accounting (probe vs pairwise-equiv)
                "dep_checks": self.window.stats.dep_checks,
                "scoreboard_probes": self.window.stats.scoreboard_probes,
            }

    def _finalize(self) -> SchedulerReport:
        wall = time.perf_counter() - self._t0
        self.stats.exec_seconds = wall
        report = SchedulerReport(self.window, self.stats, wall, self.waves)
        report.plan_mode = self.plan_mode  # type: ignore[attr-defined]
        report.session_stats = self.session_stats()  # type: ignore[attr-defined]
        report.arena_stats = {  # type: ignore[attr-defined]
            "n_classes": self.arena.n_classes(),
            "total_waste_frac": round(self.arena.total_waste_frac(), 4),
            "per_class": self.arena.padding_waste(),
            "device_steps": sum(e["plan_steps"] for e in self.epoch_log),
        }
        return report
