"""Mesh-sharded device window: per-device slab shards behind one global
admission plane (DESIGN §12).

Everything below `DeviceSession` runs on ONE device: one slab arena, one
dispatch stream, one plan cache. :class:`MeshDeviceSession` partitions the
live window across a JAX device mesh (``launch.mesh.make_window_mesh``):

* each **shard** is a full `DeviceSession` — its own arena (a shard-local
  address space), plan/program caches, and ready-queue epoch executor
  (``plan_mode="loop"`` unchanged) — pinned to one mesh device via the
  session's ``device=`` commitment, so every shard owns a dispatch
  stream;
* the **admission plane** is the outer scheduling window: producers
  submit in program order exactly as with any session, and each epoch the
  plane drains the window in program order, replays a fresh
  :class:`~.scoreboard.IntervalScoreboard` over the epoch to recover each
  task's exact RAW producers (``probe_writers``) and full RAW/WAR/WAW
  hazard set (``insert``), and **places** the task:

  1. a task with same-epoch RAW producers goes to its latest producer's
     shard (dependent chains never leave their device — the placement
     invariant the property tests pin);
  2. else any same-epoch hazard upstream (WAR/WAW) decides the same way;
  3. else **affinity**: the shard that owns (last wrote) one of the
     task's operand buffers — this keeps a decode chain whose epochs
     arrive one step at a time on its device without any same-epoch
     edge;
  4. else **priority-aware balance**: the shard with the least resident
     equal-or-more-urgent work for the task's priority bucket, total
     load as tie-break (new independent chains spread out; urgent
     chains additionally avoid piling onto a shard already busy with
     urgent work — DESIGN §13). With one priority class this is exactly
     least-loaded.

* within an epoch, tasks stream to their shards in **sub-epochs**: the
  plane walks program order and cuts a barrier only when a task touches a
  *base buffer* another shard wrote (or writes one another shard read) in
  the current sub-epoch — inside a sub-epoch no cross-shard write
  conflicts exist at whole-buffer granularity (stricter than hazards:
  disjoint row-views of one buffer must not split row ownership across
  shards), so shards dispatch independently (concurrent streams on real
  multi-device hardware);
* only true **cross-shard edges** move data, through a
  :class:`ShardLink` at sub-epoch boundaries. The link selects a
  transfer mode per session (``transfer_mode="auto"`` probes the backend
  once): **d2d** peer-copies the owning shard's slab row straight onto
  the consumer's slab (``jax.device_put`` between pinned devices — no
  host hop, the row arrives device-authoritative exactly as if the
  consumer had written it), while **staged** is the host fallback — the
  owner syncs the row back (``sync_buffers``, a counted d2h tagged
  ``mesh-transfer``), the consumer marks it host-authoritative
  (``mark_host_dirty``) and re-uploads on its next dispatch (a counted
  h2d, same tag). Rows the owner holds only host-side fall back to
  staged per-row even in d2d mode. Every copy lands in the
  :class:`~.arena.ShardTransferTable` — source/destination shard, shape
  class, bytes, mode — so the capacity claims in ``bench_serving`` are
  honest net of transfer traffic. A per-buffer copy-set memoizes clean
  replicas (a weight buffer read by many shards ships once per shard,
  not once per epoch), and a write **invalidates** every other copy
  holder's authoritative claim (``invalidate_row``) so a superseded d2d
  replica can never clobber the fresh value at a later sync.
* shard drains **overlap** (``overlap_drains=True``): a sub-epoch
  launches every involved shard's epoch back-to-back with retirement
  deferred (``DeviceSession.launch``), then retires them through a
  non-blocking round-robin ``poll_inflight`` pump — independent shards'
  dispatches are genuinely concurrent on multi-device hardware instead
  of serialized by a host-side drain loop. ``drain_overlap`` records the
  max shards simultaneously in flight; a stall raises only when a full
  round-robin pass (plus one blocking poll) advances nothing.

Placement is the CAPACITY mechanism, not just a traffic optimization: a
single interleaved window keeps re-tracing (spec subsets × shape
signatures churn epoch to epoch), while per-chain shard placement keeps
each shard's working set small and structurally stable — near-zero
steady-state compiles per shard (measured in ``bench_serving``'s
``mesh_scaling`` section) — and on multi-device hardware the per-shard
dispatch streams additionally overlap.

Bit-identity: placement only decides WHERE a task runs; ordering comes
from program order + the same interval-hazard semantics every other
session uses, so the differential matrix holds mesh == run_serial
bit-exactly at any shard count, including shard counts above the device
count (shards then share devices round-robin — the logical-shard mode the
default 1-device test environment exercises).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from . import spans
from .arena import ShardTransferTable
from .buffers import Buffer
from .device_dispatch import DeviceOpRegistry, DeviceSession
from .executors import ExecStats
from .scheduler import SchedulerReport
from .scoreboard import IntervalScoreboard
from .session import SchedulerSession
from .task import Task, operand_base

__all__ = ["MeshDeviceSession", "ShardLink"]


class ShardLink:
    """Cross-shard row mover: the transfer layer between a mesh session's
    per-device shards (DESIGN §12).

    One link per session. ``mode`` selects the path:

    * ``"d2d"`` — the owner exports its device-resident slab row
      (:meth:`DeviceSession.export_row`, a lazy slice that never blocks)
      and the destination imports it (:meth:`DeviceSession.import_row`,
      a ``jax.device_put`` peer copy committed onto the destination's
      pinned device) — no host round-trip, no ``host_syncs``;
    * ``"staged"`` — the original host hop (owner d2h, destination marks
      host-dirty and re-uploads at its next dispatch), both halves tagged
      ``mesh-transfer`` in the sync audit;
    * ``"auto"`` — probe once at construction: a trial peer copy between
      the first two distinct shard devices selects ``d2d`` if the backend
      lands it on the target device, ``staged`` otherwise (the fallback
      matrix for backends without p2p).

    Even under ``d2d``, a row whose authoritative value lives host-side
    (host-fallback writes, never-dispatched buffers) falls back to the
    staged path per-row — ``d2d_fallbacks`` counts those. Every move is
    recorded in the :class:`~.arena.ShardTransferTable` with its actual
    mode, so the byte audit stays exact on both paths.
    """

    MODES = ("auto", "d2d", "staged")

    def __init__(self, shards: Sequence[DeviceSession],
                 table: ShardTransferTable, mode: str = "auto"):
        if mode not in self.MODES:
            raise ValueError(
                f"transfer_mode must be one of {self.MODES}, got {mode!r}")
        self.shards = list(shards)
        self.table = table
        self.requested_mode = mode
        # Why the link runs in its mode: the probe's finding under "auto",
        # or that the caller forced it.
        self.mode_reason = f"forced by transfer_mode={mode!r}"
        if mode == "auto":
            ok, self.mode_reason = self._probe_p2p()
            mode = "d2d" if ok else "staged"
        self.selected_mode = mode
        self.d2d_moves = 0
        self.staged_moves = 0
        self.d2d_fallbacks = 0

    def _probe_p2p(self) -> Tuple[bool, str]:
        """One-shot backend capability probe: can a committed array move
        between two distinct shard devices with ``jax.device_put``?
        Returns ``(ok, reason)``. A single-device mesh trivially supports
        the d2d path (the peer copy degenerates to a same-device put). A
        backend without peer copies refuses the transfer with a runtime
        error, which selects ``staged``; any other exception propagates."""
        import jax
        import jax.numpy as jnp

        devs: List[Any] = []
        for sh in self.shards:
            d = sh.device
            if d is not None and all(d is not e for e in devs):
                devs.append(d)
        if not devs:
            return False, "no pinned shard devices to commit a row onto"
        if len(devs) == 1:
            return True, "one shard device: peer copies are local puts"
        try:
            probe = jax.device_put(jnp.zeros((8,), jnp.float32), devs[0])
            peer = jax.device_put(probe, devs[1])
            jax.block_until_ready(peer)
        except jax.errors.JaxRuntimeError as e:
            return False, f"peer copy {devs[0]} -> {devs[1]} refused: {e}"
        (landed,) = peer.devices()
        if landed != devs[1]:
            return False, (f"peer copy {devs[0]} -> {devs[1]} landed on "
                           f"{landed}")
        return True, f"peer copy {devs[0]} -> {devs[1]} landed"

    def move(self, base: Buffer, owner: int, dest: int) -> str:
        """Move ``base``'s row from shard ``owner`` to shard ``dest``;
        returns the mode actually used (``"d2d"`` or ``"staged"``)."""
        src, dst = self.shards[owner], self.shards[dest]
        label = src.arena.class_of(base).label
        nbytes = src.arena.row_nbytes(base)
        if self.selected_mode == "d2d":
            row = src.export_row(base)
            if row is not None and dst.import_row(base, row):
                self.d2d_moves += 1
                self.table.record(owner, dest, label, nbytes, mode="d2d")
                return "d2d"
            self.d2d_fallbacks += 1
        src.sync_buffers([base], tags=("mesh-transfer",))
        dst.mark_host_dirty(base, tag="mesh-transfer")
        self.staged_moves += 1
        self.table.record(owner, dest, label, nbytes, mode="staged")
        return "staged"

    def stats(self) -> Dict[str, Any]:
        return {
            "transfer_mode": self.selected_mode,
            "transfer_mode_requested": self.requested_mode,
            "transfer_mode_reason": self.mode_reason,
            "d2d_moves": self.d2d_moves,
            "staged_moves": self.staged_moves,
            "d2d_fallbacks": self.d2d_fallbacks,
        }


class MeshDeviceSession(SchedulerSession):
    """A live-fed session whose window is sharded across a device mesh.

    ``n_shards=None`` opens one shard per visible device (via
    ``launch.mesh.make_window_mesh``); an explicit ``n_shards`` may exceed
    the device count — shards then share devices round-robin, which keeps
    the whole path testable on a single-device host. ``devices=None``
    derives the device list from the window mesh; pass an explicit list to
    pin shards yourself. ``transfer_mode`` selects the cross-shard edge
    path (:class:`ShardLink`): ``"auto"`` probes for d2d peer copies and
    falls back to host staging, ``"d2d"``/``"staged"`` force a path (the
    benchmarks force both sides of the A/B). ``overlap_drains=False``
    reverts sub-epoch drains to the sequential one-shard-at-a-time loop
    (the overlap A/B baseline). The remaining knobs are forwarded to each
    per-shard :class:`DeviceSession`.
    """

    def __init__(
        self,
        window_size: int = 32,
        n_shards: Optional[int] = None,
        registry: Optional[DeviceOpRegistry] = None,
        plan_mode: str = "loop",
        devices: Optional[Sequence[Any]] = None,
        history_limit: Optional[int] = None,
        loop_pallas: Optional[bool] = None,
        plan_cache_limit: Optional[int] = 512,
        pad_payloads: bool = False,
        transfer_mode: str = "auto",
        overlap_drains: bool = True,
    ):
        super().__init__(window_size, history_limit=history_limit)
        if devices is None:
            from ..launch.mesh import make_window_mesh

            devices = list(make_window_mesh().devices.flat)
        if n_shards is None:
            n_shards = len(devices)
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.n_shards = n_shards
        self.devices = list(devices)
        self.registry = (registry if registry is not None
                         else DeviceOpRegistry(strict=False))
        self.plan_mode = plan_mode
        self._shards: List[DeviceSession] = [
            DeviceSession(
                window_size=window_size,
                registry=self.registry,
                plan_mode=plan_mode,
                history_limit=history_limit,
                loop_pallas=loop_pallas,
                plan_cache_limit=plan_cache_limit,
                pad_payloads=pad_payloads,
                device=self.devices[i % len(self.devices)],
            )
            for i in range(n_shards)
        ]
        # id(buffer) -> shard that last WROTE it (the owner: its slab row
        # is authoritative while device-dirty), and -> the shard set
        # holding a CURRENT copy (owner + shards a staged transfer already
        # reached). A write collapses the copy set to the writer.
        self._owner: Dict[int, int] = {}
        self._copies: Dict[int, Set[int]] = {}
        # id(buffer) -> shard that first READ it: read-only working sets
        # (tenant weights, shared tables) are never written, so write
        # ownership can't see them — the read home is what keeps a
        # tenant's requests landing where its weights already reside.
        self._read_home: Dict[int, int] = {}
        # ids of read-only buffers every shard holds its own copy of (see
        # replicate()): no shard owns them, so they never steer placement.
        self._replicated: Set[int] = set()
        # Running per-shard placement totals (the least-loaded signal),
        # plus per-shard totals broken down by priority bucket: the
        # balance branch prefers the shard with the least equal-or-more-
        # urgent work for the incoming task's bucket — priority beats
        # raw least-loaded on tie (DESIGN §13) — with the plain total as
        # tie-break so the single-class default reduces exactly to the
        # old least-loaded rule.
        self._placed: List[int] = [0] * n_shards
        self._placed_by_bucket: List[Dict[int, int]] = [
            {} for _ in range(n_shards)]
        self.transfer_table = ShardTransferTable()
        self.link = ShardLink(self._shards, self.transfer_table,
                              mode=transfer_mode)
        self.overlap_drains = overlap_drains
        # Max shards simultaneously in flight inside one sub-epoch drain —
        # the structural proof the overlapped pump actually overlaps.
        self.drain_overlap = 0
        self.cross_shard_edges = 0
        self.sub_epoch_barriers = 0
        self.epochs = 0
        self.placements: Dict[str, int] = {
            "raw_upstream": 0, "hazard_upstream": 0,
            "affinity": 0, "read_affinity": 0, "balance": 0,
        }

    # -- placement plane ---------------------------------------------------
    def _place_epoch(self, order: List[Task]) -> Dict[int, int]:
        """Decide every task's shard for one epoch (program order in).

        Replays a fresh scoreboard over just this epoch: ``probe_writers``
        (before the task's own insert) yields its exact same-epoch RAW
        producers, ``insert`` the full hazard set. Returns
        ``shard_of_tid``."""
        sb = IntervalScoreboard()
        pos: Dict[int, int] = {}
        shard_of: Dict[int, int] = {}
        for i, t in enumerate(order):
            for op in t.outputs:
                if id(operand_base(op)) in self._replicated:
                    raise ValueError(
                        f"task {t.opcode}#{t.tid} writes replicated buffer "
                        f"{operand_base(op).name!r}; replicated buffers are "
                        "read-only")
            raw = sb.probe_writers(t.read_segments)
            haz = sb.insert(t.tid, t.read_segments, t.write_segments)
            pos[t.tid] = i
            if raw:
                latest = max(raw, key=lambda tid: pos[tid])
                shard, reason = shard_of[latest], "raw_upstream"
            elif haz:
                latest = max(haz, key=lambda tid: pos[tid])
                shard, reason = shard_of[latest], "hazard_upstream"
            else:
                bids = [id(operand_base(op)) for op in
                        tuple(t.inputs) + tuple(t.outputs)
                        if id(operand_base(op)) not in self._replicated]
                owners = [self._owner[b] for b in bids if b in self._owner]
                homes = [self._read_home[b] for b in bids
                         if b in self._read_home]
                if owners:
                    # the most-represented owning shard (ties: first seen)
                    shard = max(set(owners), key=owners.count)
                    reason = "affinity"
                elif homes:
                    # read-only working-set locality (e.g. a new request
                    # whose only live-in is its tenant's weights)
                    shard = max(set(homes), key=homes.count)
                    reason = "read_affinity"
                else:
                    # Priority-aware balance: least resident urgency for
                    # this task's bucket first (so a high-priority chain
                    # lands away from other urgent work even when raw
                    # totals tie), total load second, shard index last.
                    # Single-class default: both components equal the old
                    # least-loaded count — placement unchanged.
                    bucket = t.priority
                    shard = min(
                        range(self.n_shards),
                        key=lambda s: (
                            sum(c for b, c in
                                self._placed_by_bucket[s].items()
                                if b <= bucket),
                            self._placed[s], s))
                    reason = "balance"
            shard_of[t.tid] = shard
            for op in t.inputs:
                bid = id(operand_base(op))
                if bid not in self._replicated:
                    self._read_home.setdefault(bid, shard)
            self._placed[shard] += 1
            by_bucket = self._placed_by_bucket[shard]
            by_bucket[t.priority] = by_bucket.get(t.priority, 0) + 1
            self.placements[reason] += 1
        return shard_of

    # -- cross-shard staging ----------------------------------------------
    def _stage_transfers(self, task: Task, shard: int) -> None:
        """Materialize the cross-shard edges of one task before its shard
        dispatches: every operand owned by another shard moves through the
        :class:`ShardLink` — a device-to-device row copy when the link
        selected d2d, the host-staged hop otherwise. Memoized per
        (buffer, shard) through the copy set until the next write; a write
        collapses the copy set to the writer and drops every superseded
        copy's authoritative claim (write-owner invalidation — a stale d2d
        replica must never win a later sync race against the fresh row)."""
        for op in tuple(task.inputs) + tuple(task.outputs):
            base = operand_base(op)
            bid = id(base)
            owner = self._owner.get(bid)
            if owner is not None and owner != shard:
                self.cross_shard_edges += 1
                if shard not in self._copies.get(bid, ()):
                    self.link.move(base, owner, shard)
                    self._copies.setdefault(bid, {owner}).add(shard)
        for op in task.outputs:
            base = operand_base(op)
            bid = id(base)
            for s in self._copies.get(bid, ()):
                if s != shard:
                    self._shards[s].invalidate_row(base)
            self._owner[bid] = shard
            self._copies[bid] = {shard}

    # -- the epoch ---------------------------------------------------------
    def _dispatch_sub_epoch(self, sub: List[Tuple[Task, int]]) -> None:
        """One barrier-free slice: stage its cross-shard inputs, feed each
        shard its tasks (program order preserved per shard), drain every
        involved shard, then retire through the outer plane.

        When an outer observer watches the slice (listener, per-task
        callback, or ticket), outer retirement rides each INNER session's
        per-task retirement instead of firing wholesale after the drain: a
        decode chain's callbacks must observe each intermediate slot value
        exactly as they would under `DeviceSession` — and the inner
        watchers this registers are what make the inner device path sync
        values back before the callback reads them. Unwatched slices keep
        the fast path: no per-task observation, no forced syncs, one
        wholesale retirement sweep in program order."""
        watched = bool(self._listeners) or any(
            t.tid in self._watchers or t.tid in self._tickets
            for t, _ in sub)
        involved: List[int] = []
        for task, shard in sub:
            self._stage_transfers(task, shard)
            if shard not in involved:
                involved.append(shard)
            if watched:
                self._shards[shard].submit(task, on_retire=self._note_retired)
            else:
                self._shards[shard].submit(task)
        self.waves.append([t.tid for t, _ in sub])
        if self.overlap_drains:
            self._drain_overlapped(involved)
        else:
            self._drain_sequential(involved)
        if not watched:
            for task, _ in sub:
                self._note_retired(task)

    def _drain_sequential(self, involved: List[int]) -> None:
        """The pre-overlap baseline: block each involved shard to empty in
        turn (kept as the A/B control for the overlapped pump)."""
        for shard in involved:
            sh = self._shards[shard]
            while sh.outstanding:
                before = sh.outstanding
                sh.poll()
                if sh.outstanding == before:
                    raise RuntimeError(
                        f"mesh shard {shard} stalled with "
                        f"{sh.outstanding} tasks outstanding")

    def _drain_overlapped(self, involved: List[int]) -> None:
        """Launch-all-then-poll-round-robin: every involved shard's epoch
        is dispatched back-to-back with retirement deferred
        (:meth:`DeviceSession.launch`), so independent shards' dispatches
        are in flight concurrently; a non-blocking ``poll_inflight``
        round-robin then retires segments as they land. A shard idle in
        one round is NOT a stall while others advance: only when a full
        pass progresses nothing does the pump block on the oldest pending
        shard, and only a fruitless blocking poll raises — with every
        pending shard's outstanding count in the error."""
        for shard in involved:
            self._shards[shard].launch()
        pending = [s for s in involved if self._shards[s].outstanding]
        self.drain_overlap = max(self.drain_overlap, len(pending))
        while pending:
            progressed = False
            for s in list(pending):
                sh = self._shards[s]
                if sh.poll_inflight(block=False) > 0:
                    progressed = True
                if sh.outstanding and not sh.inflight_segments:
                    # Backlog past the shard window: dispatch the next
                    # epoch (still deferred) instead of spinning on it.
                    progressed = sh.launch() or progressed
                if not sh.outstanding:
                    pending.remove(s)
                    progressed = True
            if pending and not progressed:
                sh = self._shards[pending[0]]
                if sh.poll_inflight(block=True) == 0:
                    counts = {s: self._shards[s].outstanding
                              for s in pending}
                    raise RuntimeError(
                        "mesh drain stalled: a full round-robin pass "
                        "advanced no shard; outstanding per shard: "
                        f"{counts}")
                if not sh.outstanding:
                    pending.pop(0)

    def _pump(self) -> bool:
        if self.window.idle():
            return False
        order = self.window.drain_program_order()
        shard_of = self._place_epoch(order)
        # Sub-epoch walk: cut only at cross-shard conflicts within the
        # current slice; same-shard hazards ride the shard's own window.
        # The conflict test is at BASE-BUFFER granularity, not hazard
        # (segment) granularity: two tasks writing disjoint row-views of
        # the same buffer have no hazard, but on different shards they
        # would split row ownership of one slab allocation — each shard's
        # copy partially fresh and the host image never whole. A barrier
        # sequences them so the staging protocol migrates whole rows.
        # Read-read sharing across shards stays barrier-free.
        sub: List[Tuple[Task, int]] = []
        readers: Dict[int, Set[int]] = {}  # id(base) -> shards reading
        writers: Dict[int, Set[int]] = {}  # id(base) -> shards writing
        for t in order:
            shard = shard_of[t.tid]
            rb = {id(operand_base(op)) for op in t.inputs}
            wb = {id(operand_base(op)) for op in t.outputs}
            conflict = any(s != shard
                           for b in rb | wb
                           for s in writers.get(b, ())) or \
                       any(s != shard
                           for b in wb
                           for s in readers.get(b, ()))
            if conflict:
                self._dispatch_sub_epoch(sub)
                self.sub_epoch_barriers += 1
                sub, readers, writers = [], {}, {}
            for b in rb:
                readers.setdefault(b, set()).add(shard)
            for b in wb:
                writers.setdefault(b, set()).add(shard)
            sub.append((t, shard))
        if sub:
            self._dispatch_sub_epoch(sub)
        self.epochs += 1
        return True

    # -- retirement observation --------------------------------------------
    def _pre_observe_retired(self, task: Task) -> None:
        # A late observer of an already-retired task reads the task's
        # operand values host-side: sync exactly those buffers on the
        # shards that OWN them (the owner's claim is the authoritative
        # value; non-owner copies hold the same bits), not a wholesale
        # O(shards) full-session sweep per observer.
        per_shard: Dict[int, List[Buffer]] = {}
        for op in tuple(task.inputs) + tuple(task.outputs):
            base = operand_base(op)
            owner = self._owner.get(id(base))
            if owner is not None:
                per_shard.setdefault(owner, []).append(base)
        for shard, bufs in per_shard.items():
            self._shards[shard].sync_buffers(
                bufs, tags=DeviceSession._tags_of([task]))

    def shard_of(self, buf: Buffer) -> Optional[int]:
        """The shard currently owning (last to write) ``buf``, or None if
        no shard has written it. Serving uses this for per-device slot
        accounting: a request slot's owner is the device its chain ran on."""
        with self._lock:
            return self._owner.get(id(buf))

    def replicate(self, buf: Buffer) -> None:
        """Give every shard its own copy of read-only ``buf`` on its device
        (one copy per device: the put is a no-op where the value already
        lives). Host-path tasks on a shard then read the local copy — the
        serving weights, one replica per chip — and the buffer neither
        steers placement nor moves over the link. Writing it raises."""
        with self._lock:
            self._replicated.add(id(buf))
            for sh in self._shards:
                sh.replicate(buf)

    # -- row lifecycle -----------------------------------------------------
    def release_buffer(self, buf: Buffer) -> bool:
        """Forward a producer's release to every shard (each holds its own
        row when the buffer crossed shards) and drop the ownership entry.
        True if any shard recycled a row."""
        with self._lock:
            freed = False
            for sh in self._shards:
                freed = sh.release_buffer(buf) or freed
            self._owner.pop(id(buf), None)
            self._copies.pop(id(buf), None)
            self._read_home.pop(id(buf), None)
            self._replicated.discard(id(buf))
            return freed

    # -- lifecycle ---------------------------------------------------------
    def sync(self) -> None:
        """Force every shard's device-resident values back to host."""
        with self._lock:
            for sh in self._shards:
                sh.sync()

    def flush(self) -> None:
        super().flush()
        for sh in self._shards:
            sh.flush()

    def session_stats(self) -> Dict[str, Any]:
        """Mesh counters + every shard's full ``session_stats()``. The
        aggregate keys mirror `DeviceSession`'s so benchmarks can treat
        any device-backed session uniformly; ``per_shard`` keeps the
        honest breakdown (host_syncs per shard = the transfer audit)."""
        with self._lock:
            per_shard = [sh.session_stats() for sh in self._shards]
            # The span table is process-wide: reported once, at the top.
            for entry in per_shard:
                del entry["spans"]

            def total(key: str) -> int:
                return sum(s[key] for s in per_shard)

            return {
                "plan_mode": "mesh",
                "n_shards": self.n_shards,
                "n_devices": len({id(d) for d in self.devices}),
                "epochs": self.epochs,
                "sub_epoch_barriers": self.sub_epoch_barriers,
                "cross_shard_edges": self.cross_shard_edges,
                "placements": dict(self.placements),
                "transfers": self.transfer_table.as_dict(),
                **self.link.stats(),
                "overlap_drains": self.overlap_drains,
                "drain_overlap": self.drain_overlap,
                "d2d_row_exports": total("d2d_row_exports"),
                "d2d_row_imports": total("d2d_row_imports"),
                "row_invalidations": total("row_invalidations"),
                "device_dispatches": total("device_dispatches"),
                "loop_dispatches": total("loop_dispatches"),
                "host_task_dispatches": total("host_task_dispatches"),
                "plan_cache_hits": total("plan_cache_hits"),
                "plan_cache_misses": total("plan_cache_misses"),
                "compiled_programs": total("compiled_programs"),
                "host_syncs": total("host_syncs"),
                "host_syncs_d2h": total("host_syncs_d2h"),
                "host_syncs_h2d": total("host_syncs_h2d"),
                "slab_bytes": total("slab_bytes"),
                "arena_live_rows": total("arena_live_rows"),
                "arena_free_rows": total("arena_free_rows"),
                "arena_recycled_rows": total("arena_recycled_rows"),
                "arena_compactions": total("arena_compactions"),
                "dep_checks": self.window.stats.dep_checks,
                "scoreboard_probes": self.window.stats.scoreboard_probes,
                "per_shard": per_shard,
                "spans": spans.snapshot(),
            }

    def _finalize(self) -> SchedulerReport:
        wall = time.perf_counter() - self._t0
        for sh in self._shards:
            if not sh.closed:
                sh.close()
        # Aggregate exec stats across shards for the report surface.
        stats = ExecStats()
        for sh in self._shards:
            stats.dispatches += sh.stats.dispatches
            stats.tasks_run += sh.stats.tasks_run
            stats.compiles += sh.stats.compiles
            stats.wave_widths.extend(sh.stats.wave_widths)
        report = SchedulerReport(self.window, stats, wall, self.waves)
        report.plan_mode = "mesh"  # type: ignore[attr-defined]
        report.session_stats = self.session_stats()  # type: ignore[attr-defined]
        report.arena_stats = {  # type: ignore[attr-defined]
            "n_classes": sum(sh.arena.n_classes() for sh in self._shards),
            "per_shard": [sh.arena.padding_waste() for sh in self._shards],
        }
        return report
