"""Device-resident ready-queue executor — the ACS-HW fast path (DESIGN §2 A3).

The paper's ACS-HW window dispatches a kernel the moment its upstream
count hits zero, entirely inside the accelerator; Atos keeps the same
structure as device-resident task-parallel queues and Jangda et al. key
waits on producer completion flags. This kernel is that loop as ONE
Pallas program:

* the **task table** ``[n, 5] int32`` holds each task's switch branch and
  slab addresses ``(branch, in0, in1, in2, out_row)`` — the SRAM dispatch
  table of Fig 20;
* ``dep_tbl [n, m] int32`` holds forward edges (positions that depend on
  each task, sentinel-padded with ``n``);
* ``remaining`` (the per-task upstream counters), the **ready ring** and
  the per-task **completion flags** live beside the slab; retiring a task
  decrements its dependents' counters and pushes zero-crossings onto the
  ring — no host involvement anywhere in the loop.

A grid-based dispatch (prefetched index maps) cannot express this: index
maps are fixed at launch, but the ring's contents *are* the schedule and
only exist as the loop runs. So the whole epoch executes as a single
program (``grid=(1,)``) whose ``fori_loop`` pops exactly ``n`` tasks:
program order is topological, so every edge points forward and the ring
can never starve — the i-th iteration always has a task to pop
(property-tested against the serial baseline).

Memory placement follows what Mosaic can index at run time:

* every control scalar — task table, dependency table, ring, counters,
  completion flags — lives in **SMEM** (the tables arrive as scalar
  prefetch operands, flattened to 1-D; ring and counters are SMEM
  scratch), so each pop and each dependent wake-up is a scalar load or
  store at a dynamic address;
* the slab lives in **VMEM** as ``[rows, 1, lanes]``: a task's row is a
  dynamic index on the untiled leading axis, and the row width is padded
  to whole 128-lane vregs.

Both memories are small, so the kernel has a stated cap
(:data:`MAX_TASKS`, :data:`MAX_DEPS`, :data:`MAX_SLAB_VMEM_BYTES`);
:func:`over_cap` says whether an epoch fits. Callers send epochs over the
cap to the ``lax.while_loop`` interpreter in ``core/device_dispatch.py``
instead, and count them.

Eligibility is narrow by design — one 4-byte shape class, padding-free
1-D rows, arity <= 3, one output, and every kernel fn registered in the
device registry's **switch-branch table** (the fixed HW kernel set).
Everything else runs through the structurally identical
``lax.while_loop`` interpreter; both advance the frontier in one dispatch.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ready_queue_call", "over_cap", "slab_vmem_bytes", "MAX_TASKS",
           "MAX_DEPS", "MAX_SLAB_VMEM_BYTES"]

LANES = 128
SUBLANES = 8
#: Tasks per epoch. SMEM holds about ``n * (10 + m)`` int32 words.
MAX_TASKS = 1024
#: Dependency-table width (forward edges per task).
MAX_DEPS = 16
#: The slab's VMEM footprint (input and output blocks, each double
#: buffered): ``4 * rows * 8 sublanes * lanes * 4 bytes`` must stay under
#: the 16 MiB scoped-VMEM default of a v5e core.
MAX_SLAB_VMEM_BYTES = 8 << 20


def _lanes(width: int) -> int:
    return -(-width // LANES) * LANES


def slab_vmem_bytes(rows: int, width: int) -> int:
    """VMEM the kernel's slab blocks take for a ``[rows, width]`` 4-byte
    slab: each row pads to one ``(8, lanes)`` tile, and the input and
    output blocks are each double buffered."""
    return 4 * rows * SUBLANES * _lanes(width) * 4


def over_cap(n_tasks: int, dep_width: int, rows: int,
             width: int) -> Optional[str]:
    """Why an epoch of ``n_tasks`` tasks with ``dep_width`` forward edges
    each, over a ``[rows, width]`` slab, does not fit the kernel — or None
    when it does."""
    if n_tasks > MAX_TASKS:
        return f"{n_tasks} tasks > MAX_TASKS={MAX_TASKS}"
    if dep_width > MAX_DEPS:
        return f"dependency width {dep_width} > MAX_DEPS={MAX_DEPS}"
    vmem = slab_vmem_bytes(rows, width)
    if vmem > MAX_SLAB_VMEM_BYTES:
        return (f"slab [{rows}, {width}] needs {vmem} B of VMEM > "
                f"MAX_SLAB_VMEM_BYTES={MAX_SLAB_VMEM_BYTES}")
    return None


def _ready_queue_kernel(task_ref, dep_ref, ring0_ref, rem0_ref, tail_ref,
                        slab_in_ref, slab_ref, done_ref, ring_ref, rem_ref,
                        *, n, m, branches):
    slab_ref[...] = slab_in_ref[...]

    def init(i, carry):
        ring_ref[i] = ring0_ref[i]
        rem_ref[i] = rem0_ref[i]
        return carry

    def clear(i, carry):
        done_ref[i] = 0
        return carry

    jax.lax.fori_loop(0, n + 1, init, 0)
    jax.lax.fori_loop(0, n, clear, 0)

    def body(i, tail):
        # head == i: one pop per iteration; edges point forward in program
        # order, so the ring holds at least i+1 entries by iteration i.
        t = ring_ref[i]
        row = t * 5
        x = slab_ref[task_ref[row + 1]]
        y = slab_ref[task_ref[row + 2]]
        z = slab_ref[task_ref[row + 3]]
        res = jax.lax.switch(task_ref[row], branches, x, y, z)
        slab_ref[task_ref[row + 4]] = res.astype(slab_ref.dtype)
        done_ref[t] = 1
        # Retire: decrement each dependent's counter; zero-crossings join
        # the ring at the tail. Sentinel edges (== n) hit the trash slot of
        # `remaining`/`ring` (both sized n+1), never a live counter.
        for j in range(m):
            d = dep_ref[t * m + j]
            rem = rem_ref[d] - 1
            rem_ref[d] = rem
            ready = jnp.logical_and(d < n, rem == 0)
            ring_ref[jnp.where(ready, tail, n)] = d
            tail = tail + ready.astype(jnp.int32)
        return tail

    jax.lax.fori_loop(0, n, body, tail_ref[0])


@functools.partial(jax.jit,
                   static_argnames=("branches", "interpret"))
def ready_queue_call(
    slab: jax.Array,       # [rows, d] the single shape class's slab
    task_tbl: jax.Array,   # [n, 5] int32 (branch, in0, in1, in2, out_row)
    dep_tbl: jax.Array,    # [n, m] int32 forward edges, sentinel n
    ring0: jax.Array,      # [n+1] int32: initially-ready positions, pad n
    rem0: jax.Array,       # [n+1] int32: in-degrees + one trash slot
    tail0: jax.Array,      # [1] int32: count of initially-ready tasks
    *,
    branches: Tuple[Callable, ...],  # fn(x, y, z) -> [d], arity-normalized
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run one epoch's ready-queue program; returns ``(slab', done)``
    where ``done`` is the ``[n] int32`` per-task completion-flag array
    (all ones iff the queue drained — the lowering guarantees it). The
    epoch must fit the kernel (:func:`over_cap` is None)."""
    n, m = dep_tbl.shape
    rows, width = slab.shape
    reason = over_cap(n, m, rows, width)
    if reason is not None:
        raise ValueError(f"ready-queue epoch over the kernel's cap: {reason}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lanes = _lanes(width)
    slab3 = jnp.pad(slab, ((0, 0), (0, lanes - width)))[:, None, :]
    whole = pl.BlockSpec((rows, 1, lanes), lambda i, *_: (0, 0, 0))
    slab_out, done = pl.pallas_call(
        functools.partial(_ready_queue_kernel, n=n, m=m, branches=branches),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[whole],
            out_specs=[whole, pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.SMEM((n + 1,), jnp.int32),
                            pltpu.SMEM((n + 1,), jnp.int32)],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(slab3.shape, slab.dtype),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ),
        interpret=interpret,
    )(task_tbl.astype(jnp.int32).reshape(-1),
      dep_tbl.astype(jnp.int32).reshape(-1),
      ring0.astype(jnp.int32), rem0.astype(jnp.int32),
      tail0.astype(jnp.int32), slab3)
    return slab_out[:, 0, :width], done
