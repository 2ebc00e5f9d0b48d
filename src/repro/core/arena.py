"""Shape-class slab arena — the device-resident buffer image (DESIGN §2 A3).

ACS-HW keeps the scheduling window *and* the kernels' operands next to the
command processor so dispatch never round-trips to the host. Our device
interpreter (`core/device_dispatch.py`) needs the same thing on TPU: every
operand a lowered stream touches must live in a device-resident slab that
dispatch tables can index with plain integers. The seed version supported
exactly one uniform ``(D,)`` shape; the arena generalizes it to the real
workloads:

* Operands are grouped into **shape classes** ``(padded_shape, dtype)``;
  the padded shape rounds the trailing dimension up to ``pad_multiple``
  (8 by default — one TPU sublane; use 128 to model full lane padding).
  Two buffers whose shapes pad to the same tuple share a class even when
  their true shapes differ — the per-operand true shape is static in the
  lowered program, so gathers slice the padding back off before compute.
* Each class owns one **slab** ``[rows, *padded_shape]``; every
  ``Buffer`` is assigned one row, and a row-``BufferView`` resolves to a
  leading-axis sub-interval of its parent's row, so view aliasing (a
  joint writing one row of a force buffer the integrator later reads in
  full) behaves exactly like the virtual-address-range checks in
  `core/buffers.py`. (The seed's dummy row is gone: arena steps are
  fully active — no inactive slots needing a write sink.)
* Padding is **accounted, not hidden**: ``padding_waste()`` reports, per
  class, the row count and the fraction of slab cells occupied by padding
  — the cost of running heterogeneous kernels through a uniform-indexed
  arena, which benchmarks surface next to dispatch counts.
* The arena may be **persistent** (the `DeviceSession` rolling window):
  ``pack_incremental`` keeps already-materialized slabs and appends only
  rows added since the last pack (new submissions referencing new
  buffers), and ``update_rows`` refreshes individual rows whose host
  values changed (host-fallback writes between device epochs). Row and
  class ids are stable *between compactions*, so lowered dispatch tables
  stay valid across epochs.
* Rows have a **lifecycle** (DESIGN §2 A3 — the unbounded-lifetime gap):
  ``free(buf)`` releases a buffer's row into its class's free-list, and
  ``add`` recycles free rows before growing the slab — a long-lived
  session fed per-request buffers reuses a bounded row set instead of
  leaking one row per request. Recycled rows inside the packed watermark
  are tracked and refreshed from host values at the next
  ``pack_incremental`` (the device row still holds the dead buffer's
  bits). When a class's dead-row fraction crosses ``compact_waste``
  (``needs_compaction``), ``compact`` rebuilds the class: live rows are
  renumbered densely (old order preserved), the device slab is gathered
  in place (no host round-trip for already-packed rows), and the class's
  **generation** counter bumps — the signal consumers holding static row
  addresses (the `DeviceSession` plan cache) use to invalidate exactly
  the affected entries.
* The **read-back** moves whole classes, not rows: ``unpack`` transfers
  each touched class to the host once (``unpack_transfers``) and cuts
  rows and padding in NumPy; ``unpack_on_device`` leaves device slices
  for a caller that hands the values straight to a jit call.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .buffers import Buffer, BufferView
from .task import Operand, Task, operand_base

__all__ = ["ShapeClass", "ArenaAddress", "SlabArena", "ShardTransferTable",
           "pad_shape", "row_capacity"]


def _commit_like(val: Any, slab: Any) -> Any:
    """Place ``val`` on the device ``slab`` is committed to. A persistent
    slab pinned to a non-default device (mesh shards pin each shard's
    session) must not be updated with values committed elsewhere: a
    buffer written by one shard's dispatch holds an array committed to
    THAT shard's device, and scattering it into another shard's slab
    raises jax's incompatible-devices error. Uncommitted slabs (single
    device, plain host sessions) pass through untouched."""
    if getattr(slab, "committed", False):
        (dev,) = slab.devices()
        return jax.device_put(val, dev)
    return val


@jax.jit
def _take_rows(slab: Any, idx: Any) -> Any:
    """The rows ``idx`` of ``slab``, as one device op."""
    return slab[idx]


def row_capacity(n_rows: int) -> int:
    """Physical slab rows for ``n_rows`` logical rows: the next power of
    two (floored at 8). Slab shapes are jit trace signatures — an
    exact-fit slab forces a retrace (and a full XLA compile) every time
    the resident peak moves by one row, which dominates wall time for
    small irregular kernels. Quantizing capacity bounds the distinct
    shapes per class at O(log peak); rows past the logical count hold
    zeros and are never addressed."""
    cap = 8
    while cap < n_rows:
        cap *= 2
    return cap


def pad_shape(shape: Tuple[int, ...], pad_multiple: int) -> Tuple[int, ...]:
    """Round the trailing dimension up to ``pad_multiple`` (scalars pass
    through)."""
    if not shape or pad_multiple <= 1:
        return tuple(shape)
    last = -(-shape[-1] // pad_multiple) * pad_multiple
    return tuple(shape[:-1]) + (last,)


@dataclasses.dataclass(frozen=True)
class ShapeClass:
    """One slab's identity: the padded shape every resident row shares."""

    padded_shape: Tuple[int, ...]
    dtype: str

    @property
    def row_elems(self) -> int:
        return int(np.prod(self.padded_shape, dtype=np.int64)) if self.padded_shape else 1

    @property
    def label(self) -> str:
        return f"{self.dtype}{list(self.padded_shape)}"


@dataclasses.dataclass(frozen=True)
class ArenaAddress:
    """Where one operand lives: ``slabs[class_id][row]``, optionally a
    leading-axis sub-interval ``[row_start : row_start + row_count]`` when
    the operand is a row view of its parent buffer."""

    class_id: int
    row: int
    row_start: int = 0
    row_count: int = 0  # 0 => the whole row (a full Buffer operand)

    @property
    def is_view(self) -> bool:
        return self.row_count > 0


class ShardTransferTable:
    """Cross-shard row-transfer ledger for a mesh-sharded window.

    Each shard owns its own :class:`SlabArena` — a shard-local address
    space: ``(class_id, row)`` coordinates are meaningful only against the
    owning shard's slabs, so a buffer consumed on a different shard than
    the one that produced it cannot be addressed remotely; its row is
    MOVED across at a sub-epoch boundary — either as a direct
    device-to-device peer copy of the slab row (``mode="d2d"``) or through
    the host-staged fallback (owner syncs the row to host, the destination
    refreshes it on its next dispatch; ``mode="staged"``). This table
    records every such copy — source shard, destination shard, shape-class
    label, row bytes, and transfer mode — so the mesh session can report
    cross-device traffic honestly (the paper's concurrency claims are only
    meaningful net of transfer cost).
    """

    def __init__(self) -> None:
        self.transfers = 0
        self.bytes = 0
        # (src_shard, dst_shard) -> count; class label -> count;
        # mode -> {transfers, bytes} (the d2d-vs-staged audit split).
        self.by_route: Dict[Tuple[int, int], int] = {}
        self.by_class: Dict[str, int] = {}
        self.by_mode: Dict[str, Dict[str, int]] = {}

    def record(self, src_shard: int, dst_shard: int, class_label: str,
               nbytes: int, mode: str = "staged") -> None:
        self.transfers += 1
        self.bytes += int(nbytes)
        route = (src_shard, dst_shard)
        self.by_route[route] = self.by_route.get(route, 0) + 1
        self.by_class[class_label] = self.by_class.get(class_label, 0) + 1
        slot = self.by_mode.setdefault(mode, {"transfers": 0, "bytes": 0})
        slot["transfers"] += 1
        slot["bytes"] += int(nbytes)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "transfers": self.transfers,
            "bytes": self.bytes,
            "by_route": {f"{s}->{d}": n
                         for (s, d), n in sorted(self.by_route.items())},
            "by_class": dict(sorted(self.by_class.items())),
            "by_mode": {m: dict(v)
                        for m, v in sorted(self.by_mode.items())},
        }


class SlabArena:
    """Assigns buffers to (class, row) slab coordinates and moves values
    host<->device around a lowered stream's single dispatch."""

    def __init__(self, pad_multiple: int = 8, *, compact_waste: float = 0.5,
                 compact_min_rows: int = 8):
        self.pad_multiple = pad_multiple
        # Compaction policy: rebuild a class once it holds at least
        # compact_min_rows rows and its dead fraction reaches compact_waste.
        self.compact_waste = compact_waste
        self.compact_min_rows = compact_min_rows
        self._class_ids: Dict[ShapeClass, int] = {}
        self._classes: List[ShapeClass] = []
        # per class, row -> Buffer (None = freed row awaiting reuse)
        self._rows: List[List[Optional[Buffer]]] = []
        # id(Buffer) -> (class, row); _rows holds the references, keeping
        # the ids stable between compactions.
        self._addr: Dict[int, Tuple[int, int]] = {}
        # Per-class count of rows already materialized into device slabs
        # (the pack_incremental watermark).
        self._packed_rows: List[int] = []
        # Per-class LIFO free-lists of recyclable row indices.
        self._free: List[List[int]] = []
        # Per-class rows below the packed watermark that were re-assigned to
        # a new buffer since the last pack: the device row still holds the
        # dead occupant's bits and must be refreshed at the next
        # pack_incremental.
        self._reused: List[set] = []
        # Per-class compaction counters; a cached plan built against a
        # class's addresses is valid iff the generation it recorded still
        # matches. `generation` is the global sum (cheap change detector).
        self._generation: List[int] = []
        self.generation = 0
        # Lifecycle counters (surfaced through session_stats / benchmarks).
        self.freed_rows = 0
        self.recycled_rows = 0
        self.compactions = 0
        self.unpack_rows_written = 0
        self.unpack_transfers = 0

    # -- classification ----------------------------------------------------
    def class_of(self, buf: Buffer) -> ShapeClass:
        return ShapeClass(
            padded_shape=pad_shape(tuple(buf.shape), self.pad_multiple),
            dtype=str(np.dtype(buf.dtype)),
        )

    def row_nbytes(self, buf: Buffer) -> int:
        """Padded slab-row bytes a transfer of this buffer moves — what a
        :class:`ShardTransferTable` records per staged cross-shard copy."""
        cls = self.class_of(buf)
        return cls.row_elems * np.dtype(cls.dtype).itemsize

    def add(self, buf: Buffer) -> Tuple[int, int]:
        """Assign ``buf`` a (class_id, row); idempotent per buffer object."""
        key = id(buf)
        if key in self._addr:
            return self._addr[key]
        cls = self.class_of(buf)
        cid = self._class_ids.get(cls)
        if cid is None:
            cid = len(self._classes)
            self._class_ids[cls] = cid
            self._classes.append(cls)
            self._rows.append([])
            self._packed_rows.append(0)
            self._free.append([])
            self._reused.append(set())
            self._generation.append(0)
        if self._free[cid]:
            row = self._free[cid].pop()
            self._rows[cid][row] = buf
            self.recycled_rows += 1
            if row < self._packed_rows[cid]:
                # The materialized slab row holds the previous occupant's
                # value; refresh it from host at the next incremental pack.
                self._reused[cid].add(row)
        else:
            row = len(self._rows[cid])
            self._rows[cid].append(buf)
        self._addr[key] = (cid, row)
        return cid, row

    def free(self, buf: Buffer) -> bool:
        """Release ``buf``'s row into its class free-list for recycling.

        Returns False (no-op) when the buffer is not arena-resident. The
        caller is responsible for ordering: a row must not be freed while a
        pending task still references its buffer.
        """
        addr = self._addr.pop(id(buf), None)
        if addr is None:
            return False
        cid, row = addr
        self._rows[cid][row] = None
        self._free[cid].append(row)
        self._reused[cid].discard(row)
        self.freed_rows += 1
        return True

    def add_tasks(self, tasks: Iterable[Task]) -> None:
        for t in tasks:
            for op in tuple(t.inputs) + tuple(t.outputs):
                self.add(operand_base(op))

    def address(self, op: Operand) -> ArenaAddress:
        """Resolve an operand to its arena coordinates (adding the parent
        buffer if unseen)."""
        if isinstance(op, BufferView):
            if op.row_start is None:
                raise ValueError(
                    f"arena operands must be Buffers or row views; {op.name!r} "
                    "is a raw byte view (no row_start)"
                )
            cid, row = self.add(op.buffer)
            return ArenaAddress(cid, row, op.row_start, op.row_count)
        cid, row = self.add(op)
        return ArenaAddress(cid, row)

    # -- introspection -----------------------------------------------------
    def __contains__(self, buf: Buffer) -> bool:
        """True iff ``buf`` already holds a (class, row) assignment."""
        return id(buf) in self._addr

    def addr_of(self, buf: Buffer) -> Optional[Tuple[int, int]]:
        """``(class_id, row)`` for a resident buffer, ``None`` otherwise —
        the read-only lookup transfer layers use (unlike :meth:`add`, it
        never assigns a row as a side effect)."""
        return self._addr.get(id(buf))

    # -- row-granular device transfer (mesh d2d edges) ----------------------
    def export_row(self, slabs: Sequence[Any], buf: Buffer, *,
                   expected_generation: Optional[int] = None) -> Any:
        """The materialized device row holding ``buf``'s padded value —
        the unit a :class:`ShardLink` peer-copies to another shard without
        a host round-trip. Raises if the buffer is not resident, its row
        was never packed, or the class generation moved under the caller
        (a compaction renumbered rows between address capture and export)."""
        addr = self._addr.get(id(buf))
        if addr is None:
            raise KeyError(f"export_row: {buf.name!r} is not arena-resident")
        cid, row = addr
        if expected_generation is not None and \
                self._generation[cid] != expected_generation:
            raise RuntimeError(
                f"export_row: class {cid} generation moved "
                f"{expected_generation} -> {self._generation[cid]} "
                f"(compaction invalidated the captured row address)")
        if row >= self._packed_rows[cid] or row in self._reused[cid]:
            raise RuntimeError(
                f"export_row: {buf.name!r} row {row} is not materialized "
                "device-side (unpacked or pending host refresh)")
        return slabs[cid][row]

    def import_row(self, slabs: Sequence[Any], buf: Buffer, value: Any, *,
                   expected_generation: Optional[int] = None) -> List[Any]:
        """Functionally set ``buf``'s slab row to ``value`` (a padded row
        exported from a peer shard), committing the value onto this slab's
        device — the receiving half of a d2d edge. Requires the row to be
        materialized already (inside the packed watermark); the same
        generation check as :meth:`export_row` applies."""
        addr = self._addr.get(id(buf))
        if addr is None:
            raise KeyError(f"import_row: {buf.name!r} is not arena-resident")
        cid, row = addr
        if expected_generation is not None and \
                self._generation[cid] != expected_generation:
            raise RuntimeError(
                f"import_row: class {cid} generation moved "
                f"{expected_generation} -> {self._generation[cid]} "
                f"(compaction invalidated the captured row address)")
        if row >= self._packed_rows[cid]:
            raise RuntimeError(
                f"import_row: {buf.name!r} row {row} is not materialized "
                "device-side yet (pack before importing)")
        cls = self._classes[cid]
        if tuple(value.shape) != cls.padded_shape:
            raise ValueError(
                f"import_row: {buf.name!r} expects a padded row of shape "
                f"{cls.padded_shape}, got {tuple(value.shape)}")
        out = list(slabs)
        out[cid] = out[cid].at[row].set(
            _commit_like(value.astype(out[cid].dtype), out[cid]))
        # The device row now holds the peer's bits; a pending host-refresh
        # mark would clobber them at the next pack.
        self._reused[cid].discard(row)
        return out

    @property
    def classes(self) -> List[ShapeClass]:
        return list(self._classes)

    def n_classes(self) -> int:
        return len(self._classes)

    def rows(self, class_id: int) -> List[Optional[Buffer]]:
        return list(self._rows[class_id])

    def class_generation(self, class_id: int) -> int:
        return self._generation[class_id]

    def device_address_table(self, operands: Sequence[Operand]
                             ) -> Tuple[np.ndarray, np.ndarray]:
        """Resolve operands to dense per-slot address arrays — the form the
        device-resident ready-queue program indexes with plain integers
        (DESIGN §2 A3): ``(rows, starts)``, both ``[len(operands)] int32``.
        ``rows`` is each operand's slab row; ``starts`` the leading-axis
        offset for row views (0 for full-buffer operands). Class ids and
        view extents stay static in the lowered program (they select the
        slab and the slice width), so only the row/start integers need to
        travel as device operands."""
        rows = np.zeros(len(operands), np.int32)
        starts = np.zeros(len(operands), np.int32)
        for i, op in enumerate(operands):
            addr = self.address(op)
            rows[i] = addr.row
            starts[i] = addr.row_start
        return rows, starts

    def live_rows(self, class_id: Optional[int] = None) -> int:
        if class_id is not None:
            return len(self._rows[class_id]) - len(self._free[class_id])
        return sum(len(r) for r in self._rows) - sum(len(f) for f in self._free)

    def free_rows(self, class_id: Optional[int] = None) -> int:
        if class_id is not None:
            return len(self._free[class_id])
        return sum(len(f) for f in self._free)

    def slab_bytes(self) -> int:
        """Device footprint of the slabs the next pack materializes: total
        rows (live + dead-but-unreclaimed) x padded row bytes per class."""
        total = 0
        for cid, cls in enumerate(self._classes):
            total += len(self._rows[cid]) * cls.row_elems * np.dtype(cls.dtype).itemsize
        return total

    def padding_waste(self) -> Dict[str, Dict[str, Any]]:
        """Per-class occupancy: how many slab cells hold real values vs
        trailing-dimension padding and dead (freed, not yet compacted)
        rows."""
        out: Dict[str, Dict[str, Any]] = {}
        for cid, cls in enumerate(self._classes):
            bufs = self._rows[cid]
            padded = cls.row_elems
            used = sum(
                int(np.prod(b.shape, dtype=np.int64)) if b.shape else 1
                for b in bufs if b is not None
            )
            total = padded * len(bufs)
            out[cls.label] = {
                "rows": len(bufs),
                "dead_rows": len(self._free[cid]),
                "padded_elems_per_row": padded,
                "used_elems": used,
                "waste_frac": round(1.0 - used / total, 4) if total else 0.0,
            }
        return out

    def total_waste_frac(self) -> float:
        padded = used = 0
        for cid, cls in enumerate(self._classes):
            padded += cls.row_elems * len(self._rows[cid])
            used += sum(
                int(np.prod(b.shape, dtype=np.int64)) if b.shape else 1
                for b in self._rows[cid] if b is not None
            )
        return 1.0 - used / padded if padded else 0.0

    # -- compaction ---------------------------------------------------------
    def needs_compaction(self) -> List[int]:
        """Class ids whose dead-row fraction crossed the policy threshold."""
        out = []
        for cid in range(len(self._classes)):
            total = len(self._rows[cid])
            if total >= self.compact_min_rows and \
                    len(self._free[cid]) / total >= self.compact_waste:
                out.append(cid)
        return out

    def compact(self, slabs: Optional[Sequence[Any]] = None,
                class_ids: Optional[Iterable[int]] = None,
                ) -> Tuple[Optional[List[Any]], Dict[int, Dict[int, int]]]:
        """Rebuild the given classes' slabs with dead rows squeezed out.

        Live rows keep their relative order, so already-packed rows form a
        dense prefix and the new slab is a pure device-side gather of the
        old one — freed rows' values are dropped, never round-tripped
        through the host. Rows beyond the old watermark were never
        materialized; the watermark resets to the packed-live count and the
        next :meth:`pack_incremental` appends them as usual.

        Returns ``(new_slabs, moved)`` where ``moved[cid]`` maps old row ->
        new row for every surviving row of a compacted class. Each
        compacted class's generation (and the global ``generation``) bumps,
        invalidating any consumer-cached addressing built against it.
        ``slabs=None`` skips the device gather (un-materialized arena).
        """
        if class_ids is None:
            class_ids = self.needs_compaction()
        out = None if slabs is None else list(slabs)
        moved: Dict[int, Dict[int, int]] = {}
        for cid in class_ids:
            if not self._free[cid]:
                continue
            rows = self._rows[cid]
            packed = self._packed_rows[cid]
            live_old = [r for r, b in enumerate(rows) if b is not None]
            remap = {old: new for new, old in enumerate(live_old)}
            # ascending order => packed live rows are exactly the prefix
            n_packed_live = sum(1 for r in live_old if r < packed)
            for old in live_old:
                self._addr[id(rows[old])] = (cid, remap[old])
            self._rows[cid] = [rows[r] for r in live_old]
            self._free[cid] = []
            self._reused[cid] = {remap[r] for r in self._reused[cid]}
            self._packed_rows[cid] = n_packed_live
            if out is not None and cid < len(out):
                keep = live_old[:n_packed_live]
                slab = out[cid][jnp.asarray(keep, dtype=jnp.int32)] \
                    if keep else out[cid][:0]
                # Re-pad to quantized capacity over the squeezed logical
                # rows, so the follow-up pack_incremental appends within
                # capacity instead of changing the slab shape again.
                cap = row_capacity(len(self._rows[cid]))
                if cap > slab.shape[0]:
                    cls = self._classes[cid]
                    slab = jnp.concatenate(
                        [slab,
                         jnp.zeros((cap - slab.shape[0],) + cls.padded_shape,
                                   slab.dtype)])
                out[cid] = slab
            moved[cid] = remap
            self._generation[cid] += 1
            self.generation += 1
            self.compactions += 1
        return out, moved

    # -- host <-> device movement ------------------------------------------
    @staticmethod
    def _place(val: Any, device: Optional[Any]) -> Any:
        """Commit a row value onto ``device`` before it is stacked with
        sibling rows. Host values are not guaranteed co-located: after
        another shard's :meth:`unpack_on_device` or host-path task,
        ``buf.value`` is committed to THAT shard's device — stacking two
        such rows from different shards raises jax's incompatible-devices
        error unless the consumer pins them onto its own device first."""
        if device is None:
            return val
        return jax.device_put(val, device)

    def _row_value(self, buf: Optional[Buffer], cls: ShapeClass):
        if buf is None:
            # Dead row (freed, not yet recycled/compacted): placeholder.
            return jnp.zeros(cls.padded_shape, dtype=np.dtype(cls.dtype))
        return self._padded_value(buf, cls)

    def _padded_value(self, buf: Buffer, cls: ShapeClass):
        val = buf.value
        if val is None:
            # Not-yet-produced output: program order guarantees the
            # producing step scatters before any consumer gathers.
            return jnp.zeros(cls.padded_shape, dtype=np.dtype(cls.dtype))
        val = jnp.asarray(val)
        if tuple(val.shape) != tuple(buf.shape):
            raise ValueError(
                f"buffer {buf.name!r} declares shape {tuple(buf.shape)} but "
                f"holds a value of shape {tuple(val.shape)}"
            )
        if tuple(val.shape) == cls.padded_shape:
            return val
        pads = [(0, p - s) for s, p in zip(val.shape, cls.padded_shape)]
        return jnp.pad(val, pads)

    def pack(self, device: Optional[Any] = None) -> List[Any]:
        """One device array per class: ``[rows, *padded_shape]``. Every
        row is addressable by some operand — no scratch row (all lowered
        steps are fully active). ``device`` pins each row value before
        stacking (see :meth:`_place`)."""
        slabs = []
        for cid, cls in enumerate(self._classes):
            dtype = np.dtype(cls.dtype)
            rows = [self._place(self._row_value(b, cls), device)
                    for b in self._rows[cid]]
            slab = jnp.stack(rows).astype(dtype)
            cap = row_capacity(len(rows))
            if cap > len(rows):
                slab = jnp.concatenate(
                    [slab, jnp.zeros((cap - len(rows),) + cls.padded_shape,
                                     dtype)])
            slabs.append(slab)
            self._packed_rows[cid] = len(self._rows[cid])
            self._reused[cid].clear()  # every row just re-read from host
        return slabs

    def pack_incremental(self, slabs: Optional[Sequence[Any]],
                         device: Optional[Any] = None) -> List[Any]:
        """Persistent-arena pack: keep already-materialized slab rows (they
        hold the latest device-side values) and append only rows added
        since the last pack. ``slabs=None`` degenerates to a full
        :meth:`pack`. New classes get fresh slabs; existing slabs are never
        re-read from host values — host-side changes to already-packed
        buffers go through :meth:`update_rows`. ``device`` pins appended
        and refreshed row values before stacking (see :meth:`_place`)."""
        if slabs is None:
            return self.pack(device=device)
        out: List[Any] = list(slabs)
        for cid, cls in enumerate(self._classes):
            dtype = np.dtype(cls.dtype)
            total = len(self._rows[cid])
            packed = self._packed_rows[cid] if cid < len(slabs) else 0
            if packed < total:
                fresh = jnp.stack(
                    [self._place(self._row_value(b, cls), device)
                     for b in self._rows[cid][packed:]]
                ).astype(dtype)
                if cid < len(out):
                    cap = out[cid].shape[0]
                    if total > cap:
                        new_cap = row_capacity(total)
                        out[cid] = jnp.concatenate(
                            [out[cid],
                             jnp.zeros((new_cap - cap,) + cls.padded_shape,
                                       dtype)])
                    out[cid] = out[cid].at[packed:total].set(
                        _commit_like(fresh, out[cid]))
                else:
                    cap = row_capacity(total)
                    slab = jnp.zeros((cap,) + cls.padded_shape, dtype)
                    out.append(slab.at[:total].set(fresh))
                self._packed_rows[cid] = total
            if self._reused[cid]:
                # Recycled rows inside the watermark: the slab still holds
                # the dead occupant's bits — refresh from host values.
                rows = sorted(self._reused[cid])
                vals = jnp.stack(
                    [self._place(self._row_value(self._rows[cid][r], cls),
                                 device) for r in rows]
                ).astype(dtype)
                out[cid] = out[cid].at[jnp.asarray(rows, dtype=jnp.int32)].set(
                    _commit_like(vals, out[cid]))
                self._reused[cid].clear()
        return out

    def update_rows(self, slabs: Sequence[Any],
                    buffers: Iterable[Buffer]) -> List[Any]:
        """Refresh the given buffers' slab rows from their current host
        values (functional update): the re-sync path for buffers written
        host-side between device epochs."""
        out = list(slabs)
        for buf in buffers:
            cid, row = self._addr[id(buf)]
            val = self._padded_value(buf, self._classes[cid])
            out[cid] = out[cid].at[row].set(
                _commit_like(val.astype(out[cid].dtype), out[cid]))
        return out

    def _touched(self, only: Optional[Iterable[Buffer]]
                 ) -> Dict[int, List[Tuple[int, Buffer]]]:
        """``class_id -> [(row, buffer)]`` of the rows to read back.
        ``only`` resolves through the address map — O(|only|), not
        O(total resident rows); default is every live resident row.
        Buffers already released are skipped: their rows may have been
        recycled and no host value is owed."""
        touched: Dict[int, List[Tuple[int, Buffer]]] = {}
        if only is not None:
            for buf in only:
                addr = self._addr.get(id(buf))
                if addr is not None:
                    touched.setdefault(addr[0], []).append((addr[1], buf))
            return touched
        for cid, rows in enumerate(self._rows):
            live = [(row, buf) for row, buf in enumerate(rows)
                    if buf is not None]
            if live:
                touched[cid] = live
        return touched

    def unpack(self, slabs: Sequence[Any],
               only: Optional[Iterable[Buffer]] = None) -> None:
        """Read slab rows back into host values: each buffer's value
        becomes a NumPy array of its true shape, padding sliced off.

        ``only`` restricts the read-back to the given buffers (e.g. the
        ones some task actually wrote); see :meth:`_touched`. Each touched
        class costs one device op and one device-to-host transfer (counted
        in ``unpack_transfers``), all started together: the whole slab
        when the touched rows fill at least half of it, else one gather of
        the touched rows, its index padded to :func:`row_capacity` so the
        gather compiles O(log rows) times per slab shape. Rows and padding
        are then cut on the host, each into its own copy, so that a kept
        value does not pin the whole host slab.
        """
        touched = self._touched(only)
        picks, dense = [], []
        for cid, items in touched.items():
            slab = slabs[cid]
            dense.append(2 * len(items) >= slab.shape[0])
            if dense[-1]:
                picks.append(slab)
                continue
            idx = np.zeros(row_capacity(len(items)), np.int32)
            idx[:len(items)] = [row for row, _ in items]
            picks.append(_take_rows(slab, idx))
        hosts = jax.device_get(picks)
        for items, whole, host in zip(touched.values(), dense, hosts):
            for i, (row, buf) in enumerate(items):
                cut = (row if whole else i,) + tuple(
                    slice(0, s) for s in buf.shape)
                buf.value = np.array(host[cut])  # a copy, never a view
                self.unpack_rows_written += 1
        self.unpack_transfers += len(touched)

    def unpack_on_device(self, slabs: Sequence[Any],
                         buffers: Iterable[Buffer]) -> None:
        """Point the given buffers' values at their slab rows, padding
        sliced off, without leaving the device: the read-back of a
        host-path task that feeds the values to a jit call on the same
        device. Resolves ``buffers`` as :meth:`unpack` resolves ``only``."""
        for cid, items in self._touched(buffers).items():
            cls = self._classes[cid]
            for row, buf in items:
                val = slabs[cid][row]
                if tuple(buf.shape) != cls.padded_shape:
                    val = val[tuple(slice(0, s) for s in buf.shape)]
                buf.value = val
                self.unpack_rows_written += 1
