"""Wave executors — how a set of READY kernels actually runs on the device.

On a GPU, ACS launches ready kernels into parallel streams. A TPU core runs
one program at a time, so "concurrent execution" is realized by *fusing the
ready set into one launch* (DESIGN.md §2, assumption A1):

* :class:`SerialExecutor` — one device dispatch per task, in program order.
  This is the paper's single-stream baseline.
* :class:`FusedWaveExecutor` — the ACS-SW analogue. A wave (the ready set)
  is partitioned into homogeneous groups (equal ``Task.signature``); each
  group becomes ONE vmapped call (N small kernels -> 1 batched kernel) and
  the groups are emitted into a single jitted wave program that XLA
  schedules as one launch. Compiled wave programs are cached by the wave's
  signature multiset — the "CUDA-Graph-without-reconstruction" property:
  different inputs produce different graphs, but recurring wave *shapes*
  reuse compiled artifacts (A2).
* :class:`GroupExecutor` — the frontier half-executor (DESIGN.md §9). One
  homogeneous group per launch, split into non-blocking ``launch()`` /
  ``poll()`` halves: ``launch`` rides JAX async dispatch and writes the
  *future* arrays straight into the output buffers (downstream kernels
  chain on them without host sync), ``poll`` asks the runtime whether the
  group's results have landed, and ``sync`` is the explicit blocking
  fallback — counted separately, because blocking syncs are exactly the
  §II-D overhead the frontier scheduler exists to avoid.

Dispatch counts are recorded: they are the TPU-side analogue of the kernel
launch + synchronization overheads of §II-D.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .task import Task, operand_dtype, operand_shape

__all__ = [
    "ExecStats",
    "SerialExecutor",
    "FusedWaveExecutor",
    "GroupExecutor",
    "GroupHandle",
    "group_by_signature",
]


class ExecStats:
    def __init__(self) -> None:
        self.dispatches = 0
        self.compiles = 0
        self.tasks_run = 0
        self.wave_widths: List[int] = []
        # Host-blocking device syncs (block_until_ready). Wave/serial
        # executors sync implicitly via value consumption; the frontier
        # path counts every explicit block so "syncs << dispatches" is a
        # checkable property.
        self.blocking_syncs = 0

    def as_dict(self) -> Dict[str, Any]:
        w = np.asarray(self.wave_widths or [0])
        return {
            "dispatches": self.dispatches,
            "compiles": self.compiles,
            "tasks_run": self.tasks_run,
            "waves": len(self.wave_widths),
            "mean_wave_width": float(w.mean()),
            "max_wave_width": int(w.max()),
            "blocking_syncs": self.blocking_syncs,
        }


class SerialExecutor:
    """Single-stream baseline: every kernel is its own dispatch."""

    def __init__(self) -> None:
        self.stats = ExecStats()
        self._jit_cache: Dict[Tuple, Callable] = {}

    def execute_wave(self, tasks: Sequence[Task]) -> None:
        for task in tasks:
            self.run_task(task, task.input_values())

    def run_task(self, task: Task, values: Sequence[Any]) -> None:
        """Dispatch one task on the given input values (its buffers'
        values, or copies of them committed to the device it must run on)
        and write its outputs."""
        fn = self._jit_cache.get(task.signature)
        if fn is None:
            fn = jax.jit(task.fn)
            self._jit_cache[task.signature] = fn
            self.stats.compiles += 1
        task.write_outputs(fn(*values))
        self.stats.dispatches += 1
        self.stats.tasks_run += 1
        self.stats.wave_widths.append(1)

    def finalize(self) -> None:
        jax.block_until_ready(jax.numpy.zeros(()))


def group_by_signature(tasks: Sequence[Task]) -> List[List[Task]]:
    """Partition tasks into homogeneous (batchable) groups, oldest-first."""
    groups: Dict[Tuple, List[Task]] = {}
    order: List[Tuple] = []
    for t in tasks:
        key = t.signature
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(t)
    return [groups[k] for k in order]


_group_by_signature = group_by_signature  # backwards-compat alias


class FusedWaveExecutor:
    """ACS-SW on TPU: the ready set becomes one fused, batched launch."""

    def __init__(self) -> None:
        self.stats = ExecStats()
        self._wave_cache: Dict[Tuple, Callable] = {}

    # wave signature = ordered multiset of task signatures
    @staticmethod
    def _wave_key(groups: List[List[Task]]) -> Tuple:
        return tuple((g[0].signature, len(g)) for g in groups)

    @staticmethod
    def _build_wave_fn(groups: List[List[Task]]) -> Callable:
        metas = []
        for g in groups:
            metas.append((g[0].fn, len(g) > 1))

        def wave_fn(group_inputs):
            outs = []
            for (fn, batched), ins in zip(metas, group_inputs):
                if batched:
                    outs.append(jax.vmap(fn)(*ins))
                else:
                    outs.append(fn(*ins))
            return outs

        return jax.jit(wave_fn)

    def execute_wave(self, tasks: Sequence[Task]) -> None:
        if not tasks:
            return
        groups = _group_by_signature(tasks)
        key = self._wave_key(groups)
        wave_fn = self._wave_cache.get(key)
        if wave_fn is None:
            wave_fn = self._build_wave_fn(groups)
            self._wave_cache[key] = wave_fn
            self.stats.compiles += 1

        group_inputs = []
        for g in groups:
            if len(g) > 1:
                n_in = len(g[0].inputs)
                stacked = tuple(
                    jax.numpy.stack([t.input_values()[i] for t in g]) for i in range(n_in)
                )
                group_inputs.append(stacked)
            else:
                group_inputs.append(g[0].input_values())

        group_outputs = wave_fn(group_inputs)
        self.stats.dispatches += 1
        self.stats.tasks_run += len(tasks)
        self.stats.wave_widths.append(len(tasks))

        for g, outs in zip(groups, group_outputs):
            if len(g) > 1:
                # outs: stacked along axis 0 (single-output) or tuple thereof
                if isinstance(outs, (tuple, list)):
                    for i, t in enumerate(g):
                        t.write_outputs(tuple(o[i] for o in outs))
                else:
                    for i, t in enumerate(g):
                        t.write_outputs(outs[i])
            else:
                g[0].write_outputs(outs)

    def finalize(self) -> None:
        jax.block_until_ready(jax.numpy.zeros(()))


class GroupHandle:
    """An in-flight homogeneous group: the launch's raw result arrays plus
    the tasks whose window slots it still occupies."""

    __slots__ = ("tasks", "raw_outputs", "t_launch")

    def __init__(self, tasks: Sequence[Task], raw_outputs: List[Any], t_launch: float):
        self.tasks = list(tasks)
        self.raw_outputs = raw_outputs  # flat list of jax arrays (futures)
        self.t_launch = t_launch


def _is_ready(arr: Any) -> bool:
    is_ready = getattr(arr, "is_ready", None)
    if is_ready is None:
        return True  # no async introspection: treat dispatch as landed
    return bool(is_ready())


class GroupExecutor:
    """Non-blocking group launches for the frontier scheduler.

    ``launch`` dispatches one homogeneous group (vmapped when width > 1)
    and immediately writes the un-materialized result arrays into the
    output buffers: JAX async dispatch makes them futures, and any
    downstream kernel consuming those buffers chains on-device without a
    host round-trip. ``poll`` is the non-blocking completion probe;
    ``sync`` is the blocking fallback (counted in ``stats.blocking_syncs``).

    ``warm`` is the compile-ahead half: building a group's jitted callable
    while *other* groups execute hides compilation behind device time
    (DESIGN.md §9 double-buffering).

    The executor owns the **in-flight ledger**: ``launch`` appends to
    ``inflight`` (oldest first) and ``poll_landed``/``sync_oldest`` consume
    it. Keeping the ledger here — not in a scheduler run loop — is what
    lets groups stay in flight *across session submissions* (DESIGN.md
    §10): a live session launches, returns to its producer, and retires
    the group on a later ``poll`` with nothing lost in between. One live
    session per executor.
    """

    def __init__(self) -> None:
        self.stats = ExecStats()
        self._fn_cache: Dict[Tuple, Callable] = {}
        self.inflight: Deque[GroupHandle] = collections.deque()

    # -- compile-ahead -----------------------------------------------------
    @staticmethod
    def _abstract_inputs(group: Sequence[Task]) -> List[Any]:
        t = group[0]
        batch = (len(group),) if len(group) > 1 else ()
        return [
            jax.ShapeDtypeStruct(batch + operand_shape(x), operand_dtype(x))
            for x in t.inputs
        ]

    def warm(self, group: Sequence[Task]) -> Callable:
        """Eager compile (jax.jit alone is lazy — tracing+XLA would
        otherwise happen inside ``launch`` and stall the dispatch loop).
        Warming calls the jitted fn once on zero-filled arrays of the
        group's shapes: that populates the wrapper's own dispatch cache, so
        real launches stay on jit's C++ fast path (an AOT
        ``lower().compile()`` executable would dispatch through the slower
        Python path on every launch). The dummy work is tiny and async."""
        key = (group[0].signature, len(group) > 1)
        fn = self._fn_cache.get(key)
        if fn is None:
            base = group[0].fn
            fn = jax.jit(jax.vmap(base)) if len(group) > 1 else jax.jit(base)
            try:
                fn(*(jax.numpy.zeros(s.shape, s.dtype)
                     for s in self._abstract_inputs(group)))
            except Exception:
                pass  # fall back to compile-at-first-launch
            self._fn_cache[key] = fn
            self.stats.compiles += 1
        return fn

    # -- non-blocking halves -----------------------------------------------
    def launch(self, group: Sequence[Task]) -> GroupHandle:
        fn = self.warm(group)
        if len(group) > 1:
            n_in = len(group[0].inputs)
            vals = [t.input_values() for t in group]
            stacked = tuple(
                jax.numpy.stack([v[i] for v in vals]) for i in range(n_in)
            )
            outs = fn(*stacked)
            raw: List[Any] = []
            if isinstance(outs, (tuple, list)):
                for i, t in enumerate(group):
                    vals = tuple(o[i] for o in outs)
                    t.write_outputs(vals)
                    raw.extend(jax.tree_util.tree_leaves(vals))
            else:
                for i, t in enumerate(group):
                    t.write_outputs(outs[i])
                raw.append(outs)
        else:
            outs = fn(*group[0].input_values())
            group[0].write_outputs(outs)
            # leaves, not top-level elements: pytree-valued outputs (e.g.
            # serving cache tuples) must expose their arrays to poll()
            raw = jax.tree_util.tree_leaves(outs)
        self.stats.dispatches += 1
        self.stats.tasks_run += len(group)
        self.stats.wave_widths.append(len(group))
        handle = GroupHandle(group, raw, time.perf_counter())
        self.inflight.append(handle)
        return handle

    def poll(self, handle: GroupHandle) -> bool:
        """True iff every result of the group has landed on device."""
        return all(_is_ready(a) for a in handle.raw_outputs)

    def poll_landed(self) -> List[GroupHandle]:
        """Remove and return every in-flight group whose results have
        landed (non-blocking) — the session's rolling-retire probe."""
        landed: List[GroupHandle] = []
        still: Deque[GroupHandle] = collections.deque()
        for handle in self.inflight:
            if self.poll(handle):
                landed.append(handle)
            else:
                still.append(handle)
        self.inflight = still
        return landed

    def sync(self, handle: GroupHandle) -> None:
        """Blocking fallback: wait for the group (the §II-D overhead)."""
        jax.block_until_ready(handle.raw_outputs)
        self.stats.blocking_syncs += 1
        try:
            self.inflight.remove(handle)
        except ValueError:
            pass  # already consumed via poll_landed/sync_oldest

    def sync_oldest(self) -> Optional[GroupHandle]:
        """Blocking-sync the oldest in-flight group (its downstreams have
        waited longest); None when nothing is in flight."""
        if not self.inflight:
            return None
        handle = self.inflight.popleft()
        self.sync(handle)
        return handle

    def finalize(self) -> None:
        jax.block_until_ready(jax.numpy.zeros(()))
