"""Serving through the ACS window: a live session server + batch baseline.

Each request owns a KV-cache slot and emits kernels exactly like the
paper's applications:

* ``prefill(slot)``  — one task per newly admitted request; reads the
  token buffer, writes that slot's cache buffer.
* ``decode(slots)``  — one task over the currently decodable slot set;
  reads and writes those slots' caches.

Because slots are disjoint buffers, the ACS window discovers that a new
request's prefill is independent of the in-flight decode and co-schedules
them — continuous batching *emerges from dependency scheduling* rather
than being hand-coded. A slot's prefill -> decode -> decode chain stays
serialized by its RAW hazards on the slot buffer.

Two servers share the slot/admission machinery (:class:`_ServingCore`):

* :class:`SessionServer` — the open-loop runtime (DESIGN.md §10). It owns
  a persistent :class:`~..core.session.SchedulerSession`; admission emits
  a request's *whole program* (prefill + its count-bounded per-slot decode
  chain) through a live per-request ``TaskStream`` (``sink=`` the session,
  ``tag=req{rid}``) *into the live window while other requests' chains are
  still in flight*; per-task retirement callbacks harvest tokens and free
  prompt buffers without ever draining the world.
* :class:`ContinuousBatchingServer` — the per-step batch-drain baseline
  (``step()`` rebuilds a stream and blocks the host each iteration). Kept
  for its API stability and as the latency baseline ``bench_serving.py``
  measures the session server against.

Both apply multi-tenant QoS admission (DESIGN.md §13): requests carry a
priority class (lower = more urgent) and an optional relative deadline;
tenants may have hard slot quotas and weighted shares. ``_pick_next``
orders the queue by (aged effective priority, weighted tenant load,
deadline, arrival) — with the defaults (one priority class, unit weights,
no quotas/deadlines) this reduces exactly to the original fairness rule
(fewest active slots, oldest-first tie-break). Aging promotes a waiting
request one bucket per ``aging_s`` seconds, so a low-priority tenant's
wait behind a flood is bounded by ``priority * aging_s`` plus one
admission cycle. Backpressure is unchanged (bounded admission FIFO;
``submit`` raises :class:`AdmissionQueueFull` at capacity and stamps the
observed queue depth on the request), and both servers free each
request's prompt buffer once its prefill has retired — a long-running
server cannot leak one ``req{rid}_prompt`` allocation per request.

:class:`SessionServer` can additionally preempt long decode chains
cooperatively (``preempt_rounds``): chains are emitted in bounded
segments, and at each segment boundary — an epoch boundary under the
device/mesh schedulers — a chain yields its slot to a strictly more
urgent queued request, parking its opaque ``(cache, token, pos)`` slot
state and resuming later from exactly where it left off (the stale-slot
reset machinery makes the handoff safe; no recompute).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Deque, Dict, List, Optional, Union

import jax.numpy as jnp
import numpy as np

from ..core import BufferPool, TaskStream, WaveScheduler
from ..core.executors import SerialExecutor
from ..core.wrapper import AcsKernel
from ..models import decode_step, init_cache, prefill
from ..models.config import ArchConfig

__all__ = ["Request", "AdmissionQueueFull", "DrainTimeout",
           "ContinuousBatchingServer", "SessionServer", "INVALID_TOKEN",
           "PRIORITY_HIGH", "PRIORITY_NORMAL", "PRIORITY_LOW"]

_rid = itertools.count()

# QoS priority classes (lower = more urgent). Any non-negative int is a
# valid class; these three are the conventional named tiers.
PRIORITY_HIGH = 0
PRIORITY_NORMAL = 1
PRIORITY_LOW = 2


class AdmissionQueueFull(RuntimeError):
    """submit() refused: the bounded admission FIFO is at capacity — the
    server's backpressure signal to producers."""


class DrainTimeout(RuntimeError):
    """``run_until_drained`` exhausted ``max_iters`` with work still
    queued or active. Carries the stuck state so operators see *what*
    stalled instead of a silently truncated result list."""

    def __init__(self, message: str, *, queue_depth: int, active_slots: int,
                 finished: Optional[List["Request"]] = None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.active_slots = active_slots
        # requests that DID finish before the stall — not lost with the raise
        self.finished = finished or []


@dataclasses.dataclass
class Request:
    prompt: np.ndarray                  # [S] int32
    max_new: int = 8
    tenant: str = "default"
    priority: int = PRIORITY_NORMAL     # QoS class, lower = more urgent
    deadline: Optional[float] = None    # SLO: seconds after arrival, or None
    rid: int = dataclasses.field(default_factory=lambda: next(_rid))
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    t_arrival: float = 0.0              # perf_counter at submit
    t_admit: float = 0.0                # perf_counter when a slot was granted
    t_finish: float = 0.0               # perf_counter when the last token retired
    queue_depth: int = 0                # admission FIFO depth observed at submit
    preemptions: int = 0                # times this request's chain was parked
    rounds_left: int = 0                # decode rounds not yet emitted/retired
    parked_state: Optional[tuple] = None  # opaque (cache, tok, pos) while parked

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new

    @property
    def finished(self) -> bool:
        """True once the request's last token has retired (``t_finish``
        is stamped exactly once, at finish)."""
        return self.t_finish > 0.0

    @property
    def latency(self) -> Optional[float]:
        """End-to-end request latency, or None until finished. (It used
        to return ``-t_arrival`` — a large negative number — when read
        before finish, silently poisoning percentile aggregations.)"""
        if not self.finished:
            return None
        return self.t_finish - self.t_arrival


#: The token a serving kernel emits when any logit of the step is not
#: finite — outside ``[0, vocab)``, so a broken model never passes for one
#: that sampled a real token.
INVALID_TOKEN = -1


def _next_token(logits, vocab: int):
    """Greedy token of the last position, or :data:`INVALID_TOKEN` for a
    row with any non-finite logit."""
    last = logits[:, -1, :vocab]
    return jnp.where(jnp.all(jnp.isfinite(last), axis=-1),
                     jnp.argmax(last, axis=-1), INVALID_TOKEN)


class _ServingCore:
    """Slots, kernels, and QoS bounded admission — shared by both servers.

    QoS knobs (all default to the pre-QoS behavior):

    * ``tenant_weights`` — weighted shares: a tenant's load for admission
      purposes is ``active_slots / weight``, so weight 2.0 holds twice
      the slots of weight 1.0 at equal queue pressure.
    * ``tenant_quota`` — hard cap on a tenant's concurrently active
      slots; an int applies to every tenant, a dict caps only the listed
      tenants. Quota'd-out requests stay queued (never dropped).
    * ``aging_s`` — starvation bound: a queued request's *effective*
      priority improves one bucket per ``aging_s`` seconds waited
      (clamped at ``PRIORITY_HIGH``), so any request reaches the top
      bucket within ``priority * aging_s`` seconds. ``None`` disables.
    """

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 4,
                 max_len: int = 64, max_queue: int = 256,
                 history_limit: Optional[int] = 1024,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 tenant_quota: Optional[Union[int, Dict[str, int]]] = None,
                 aging_s: Optional[float] = 5.0):
        assert cfg.frontend is None, "serving driver uses token models"
        self.cfg = cfg
        self.max_len = max_len
        self.max_queue = max_queue
        self.history_limit = history_limit
        self.tenant_weights = dict(tenant_weights or {})
        for t, w in self.tenant_weights.items():
            if not w > 0:
                raise ValueError(f"tenant weight must be > 0: {t!r} -> {w}")
        self.tenant_quota = tenant_quota
        if aging_s is not None and not aging_s > 0:
            raise ValueError(f"aging_s must be > 0 or None, got {aging_s}")
        self.aging_s = aging_s
        self.preemptions = 0  # chains parked at a segment boundary (server-wide)
        self.pool = BufferPool()
        self.queue: Deque[Request] = collections.deque()
        self.active: Dict[int, Request] = {}
        # Incremental per-tenant active-slot counts, maintained at
        # _grant_slot / _release_slot — _pick_next used to rebuild this
        # dict from self.active on EVERY admission (O(active x queue)
        # per grant).
        self._tenant_active: Dict[str, int] = {}
        # Rolling report trace: a long-lived server's host memory must be
        # flat, so monitoring state rotates instead of accumulating
        # (asserted by benchmarks/bench_soak.py).
        self.report_log: Deque[Dict] = collections.deque(maxlen=history_limit)

        # The weights reach every prefill and decode program as an
        # operand, never through a closure: jit lowers a closed-over array
        # into the program as a constant, which at published widths would
        # copy the whole model into each compiled program. Every serving
        # task reads this one buffer; read-after-read is no hazard, so it
        # serializes nothing.
        self.weights = self.pool.alloc((1,), np.float32, name="weights",
                                       value=params)

        # one opaque buffer per slot: value = (cache pytree, last_token, pos)
        self.slots = []
        for i in range(max_slots):
            cache = init_cache(cfg, 1, max_len)
            buf = self.pool.alloc((1,), np.float32, name=f"slot{i}",
                                  value=(cache, None, 0))
            self.slots.append(buf)
        self.free = list(range(max_slots))

        cfg_ = cfg

        def _prefill_fn(slot_val, params, tokens):
            cache, _, _ = slot_val
            logits, cache = prefill(params, cfg_, tokens, cache)
            # list-of-one: each element maps to one output buffer
            return [(cache, _next_token(logits, cfg_.vocab),
                     jnp.asarray(tokens.shape[1], jnp.int32))]

        def _decode_fn(params, *slot_vals):
            outs = []
            for cache, tok, pos in slot_vals:
                pos = jnp.asarray(pos, jnp.int32)
                logits, cache = decode_step(
                    params, cfg_, tok[:, None], cache, pos,
                )
                outs.append((cache, _next_token(logits, cfg_.vocab), pos + 1))
            return outs

        self._prefill_kernel = AcsKernel(name="req_prefill", fn=_prefill_fn)
        self._decode_kernel = AcsKernel(name="req_decode", fn=_decode_fn)

    # -- client API ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int = 8,
               tenant: str = "default", priority: int = PRIORITY_NORMAL,
               deadline: Optional[float] = None) -> Request:
        """Enqueue a request. Raises :class:`AdmissionQueueFull` when the
        bounded FIFO is at capacity and :class:`ValueError` for requests
        that can never be served (over-long prompt, negative ``max_new``,
        negative ``priority``, non-positive ``deadline``); otherwise
        stamps the observed queue depth on the request (the
        producer-visible backpressure signal). ``max_new=0`` is valid and
        means zero decode rounds: the request finishes with no generated
        tokens once its prefill retires. ``priority`` is the QoS class
        (lower = more urgent, default :data:`PRIORITY_NORMAL`);
        ``deadline`` is a relative SLO in seconds — once half the budget
        is gone the request is promoted to the top bucket."""
        if len(self.queue) >= self.max_queue:
            raise AdmissionQueueFull(
                f"admission queue at capacity ({self.max_queue}); retry later")
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) > self.max_len - 1:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the cache capacity "
                f"(max_len - 1 = {self.max_len - 1}); truncate the prompt "
                "or raise max_len")
        if max_new < 0:
            raise ValueError(f"max_new must be >= 0, got {max_new}")
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        if deadline is not None and not deadline > 0:
            raise ValueError(f"deadline must be > 0 seconds, got {deadline}")
        req = Request(prompt=prompt, max_new=max_new, tenant=tenant,
                      priority=priority, deadline=deadline)
        req.t_arrival = time.perf_counter()
        self.queue.append(req)
        req.queue_depth = len(self.queue)
        return req

    def queue_depth(self) -> int:
        return len(self.queue)

    # -- admission ----------------------------------------------------------
    def _quota_of(self, tenant: str) -> Optional[int]:
        if self.tenant_quota is None:
            return None
        if isinstance(self.tenant_quota, dict):
            return self.tenant_quota.get(tenant)
        return self.tenant_quota

    def _weight_of(self, tenant: str) -> float:
        return self.tenant_weights.get(tenant, 1.0)

    def effective_priority(self, req: Request,
                           now: Optional[float] = None) -> int:
        """The request's priority bucket *as scheduled*: the submitted
        class, improved one bucket per ``aging_s`` seconds waited
        (starvation bound), promoted to the top bucket once half its
        deadline budget is spent, clamped at :data:`PRIORITY_HIGH` —
        an aged request ties the top class but never outranks it."""
        if now is None:
            now = time.perf_counter()
        bucket = req.priority
        if self.aging_s is not None:
            bucket -= int((now - req.t_arrival) / self.aging_s)
        if req.deadline is not None:
            slack = (req.t_arrival + req.deadline) - now
            if slack <= 0.5 * req.deadline:
                bucket = PRIORITY_HIGH
        return max(bucket, PRIORITY_HIGH)

    def _admission_key(self, req: Request, now: float):
        """Total admission order: most urgent effective bucket, then
        least weighted tenant load, then earliest absolute deadline,
        then arrival order (rid is monotone in submit order and survives
        preemption re-queues, so a parked request keeps its age)."""
        deadline_at = (req.t_arrival + req.deadline
                       if req.deadline is not None else float("inf"))
        load = self._tenant_active.get(req.tenant, 0) / self._weight_of(req.tenant)
        return (self.effective_priority(req, now), load, deadline_at, req.rid)

    def _pick_next(self) -> Optional[Request]:
        """QoS admission: pop the queued request minimizing
        :meth:`_admission_key`, skipping tenants at their quota. Returns
        None when every queued request is quota-blocked (callers stop
        admitting; the requests stay queued). With the default knobs —
        one priority class, unit weights, no quotas/deadlines — the key
        degenerates to (tenant active count, arrival), i.e. exactly the
        original fewest-active-slots / oldest-first scan, but against
        incremental counts: O(queue) per grant instead of
        O(active x queue).

        Under cooperative preemption (``preempt_rounds`` set) admission
        additionally holds back requests strictly less urgent than the
        most urgent ACTIVE class: a chain that just yielded at a segment
        boundary must not be re-admitted into the slot it freed while
        the urgent work it yielded to is still running (priority
        isolation — aging re-levels parked chains, so the hold-back is
        starvation-bounded like every other ordering here)."""
        now = time.perf_counter()
        floor = None
        if getattr(self, "preempt_rounds", None) is not None and self.active:
            floor = min(self.effective_priority(r, now)
                        for r in self.active.values())
        best_i: Optional[int] = None
        best_key = None
        for i, r in enumerate(self.queue):
            quota = self._quota_of(r.tenant)
            if quota is not None and self._tenant_active.get(r.tenant, 0) >= quota:
                continue
            if floor is not None and self.effective_priority(r, now) > floor:
                continue
            key = self._admission_key(r, now)
            if best_key is None or key < best_key:
                best_i, best_key = i, key
        if best_i is None:
            return None
        if best_i == 0:
            return self.queue.popleft()
        req = self.queue[best_i]
        del self.queue[best_i]
        return req

    def _grant_slot(self, req: Request):
        """Bind the request to a free slot; returns its prompt buffer
        (freed again when the prefill retires), or None when resuming a
        preempted chain — the parked ``(cache, tok, pos)`` is restored
        verbatim and no prefill is needed. For fresh admissions the slot
        value resets to ``(cache, None, 0)`` so the previous occupant's
        leftover token/pos can never be mistaken for this request's state
        (a stale token made the batch server schedule a decode before the
        new prefill retired)."""
        req.slot = self.free.pop(0)
        if req.t_admit == 0.0:  # first grant only: resume keeps the original
            req.t_admit = time.perf_counter()
        self.active[req.slot] = req
        self._tenant_active[req.tenant] = \
            self._tenant_active.get(req.tenant, 0) + 1
        if req.parked_state is not None:
            self.slots[req.slot].value = req.parked_state
            req.parked_state = None
            return None
        cache = self.slots[req.slot].value[0]
        self.slots[req.slot].value = (cache, None, 0)
        tok_buf = self.pool.alloc(
            (1, len(req.prompt)), np.int32, name=f"req{req.rid}_prompt",
            value=jnp.asarray(req.prompt[None]),
        )
        return tok_buf

    def _release_slot(self, s: int) -> Request:
        """Unbind slot ``s``: drop it from the active set, decrement the
        tenant's incremental count, return the slot to the free list.
        Every slot-freeing path (finish, harvest, zero-round finish,
        preemption park) funnels through here so the counts _pick_next
        reads can never drift from ``self.active``."""
        req = self.active.pop(s)
        n = self._tenant_active.get(req.tenant, 0) - 1
        if n > 0:
            self._tenant_active[req.tenant] = n
        else:
            self._tenant_active.pop(req.tenant, None)
        self.free.append(s)
        return req

    def _harvest_slot(self, s: int) -> Optional[Request]:
        """Read the slot's freshly decoded token; return the request if it
        finished (slot freed), else None."""
        req = self.active[s]
        _, tok, pos = self.slots[s].value
        req.generated.append(int(np.asarray(tok)[0]))
        if req.done or int(pos) >= self.max_len - 1:
            req.t_finish = time.perf_counter()
            self._release_slot(s)
            return req
        return None


class ContinuousBatchingServer(_ServingCore):
    """Per-step batch-drain serving (the seed design, and the baseline the
    session server is benchmarked against): every iteration rebuilds a
    ``TaskStream``, runs it to empty through a closed-batch scheduler, and
    blocks the host — iteration *i*'s decode can never overlap iteration
    *i+1*'s prefill."""

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 4,
                 max_len: int = 64, window: int = 32, max_queue: int = 256,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 tenant_quota: Optional[Union[int, Dict[str, int]]] = None,
                 aging_s: Optional[float] = 5.0):
        super().__init__(cfg, params, max_slots=max_slots, max_len=max_len,
                         max_queue=max_queue, tenant_weights=tenant_weights,
                         tenant_quota=tenant_quota, aging_s=aging_s)
        # slot values are opaque pytrees (cache trees): the fused vmap
        # batcher needs array operands, so waves execute via the serial
        # executor — the window still builds multi-task waves, which is
        # the dependency-schedule evidence the benchmarks read.
        self.scheduler = WaveScheduler(window_size=window,
                                       executor=SerialExecutor())

    def step(self) -> List[Request]:
        """One server iteration: admit + prefill new requests, decode the
        active set — all through the ACS window. Returns finished requests."""
        stream = TaskStream()

        # admit as many queued requests as there are free slots (stop
        # early if everything still queued is quota-blocked)
        prompt_bufs: List[str] = []
        while self.queue and self.free:
            req = self._pick_next()
            if req is None:
                break
            tok_buf = self._grant_slot(req)
            prompt_bufs.append(tok_buf.name)
            self._prefill_kernel.launch(
                stream, inputs=(self.slots[req.slot], self.weights, tok_buf),
                outputs=(self.slots[req.slot],),
            )

        # decode wave over slots that hold a token AND can still take a
        # round (not done — max_new=0 finishes on prefill alone — and not
        # at cache capacity)
        decoding = [s for s, r in self.active.items()
                    if self.slots[s].value[1] is not None and not r.done
                    and int(self.slots[s].value[2]) < self.max_len - 1]
        if decoding:
            bufs = tuple(self.slots[s] for s in decoding)
            self._decode_kernel.launch(stream, inputs=(self.weights,) + bufs,
                                       outputs=bufs)

        if not stream.tasks:
            return []
        # executors jit/cache by signature; opaque pytree values need the
        # plain (uncompiled) path — dispatch counting still applies.
        report = self.scheduler.run(stream.tasks)
        # prefills completed inside the drain: release the prompt buffers
        for name in prompt_bufs:
            self.pool.free(name)
        entry = report.as_dict()
        entry["tasks_this_run"] = sum(len(w) for w in report.waves)
        entry["waves_this_run"] = len(report.waves)
        self.report_log.append(entry)

        finished = []
        for s in list(decoding):
            req = self._harvest_slot(s)
            if req is not None:
                finished.append(req)
        # zero-round finish: active slots whose prefill retired but which
        # can never decode (max_new=0, or the prompt fills the cache) —
        # finish with what they have instead of spinning forever
        for s in list(self.active):
            req = self.active[s]
            _, tok, pos = self.slots[s].value
            if tok is not None and (
                    req.done or int(pos) >= self.max_len - 1):
                req.t_finish = time.perf_counter()
                self._release_slot(s)
                finished.append(req)
        return finished

    def run_until_drained(self, max_iters: int = 200) -> List[Request]:
        """Step until queue and slots are empty. Raises
        :class:`DrainTimeout` (carrying the stuck queue/active counts and
        the requests that DID finish) if ``max_iters`` steps don't drain
        the server — it used to return the partial list silently."""
        out: List[Request] = []
        for _ in range(max_iters):
            out.extend(self.step())
            if not self.queue and not self.active:
                return out
        raise DrainTimeout(
            f"run_until_drained: {max_iters} steps left "
            f"{len(self.queue)} queued / {len(self.active)} active requests",
            queue_depth=len(self.queue), active_slots=len(self.active),
            finished=out)


class SessionServer(_ServingCore):
    """Open-loop serving on a persistent scheduler session (DESIGN.md §10).

    Admission emits a request's *entire* kernel program — prefill plus its
    count-bounded decode chain — into the live window while other
    requests' chains are still in flight; the window's RAW hazards
    serialize each chain on its own slot buffer and co-schedule
    independent chains. ``pump()`` is the non-blocking service iteration:
    poll the session (retirement callbacks harvest tokens, free prompt
    buffers, finish requests), then admit queued requests into freed
    slots. Admission latency is bounded by the pump cadence, not by a full
    window drain, and no mid-request host round-trip ever gates a decode
    chain.

    ``scheduler="frontier"`` (default) runs width-1 groups through the
    async frontier — slot values are opaque pytrees, which vmap cannot
    stack, so concurrency comes from overlapped in-flight groups rather
    than batching. ``scheduler="wave"`` reproduces the seed's fused-wave
    evidence (one slot's decode co-resident with another's prefill in a
    single wave) with a serial executor. ``scheduler="device"`` serves
    through the persistent :class:`~..core.device_dispatch.DeviceSession`:
    admitted chains drain in whole-window epochs (slot values are opaque
    cache pytrees, so every serving kernel takes the session's in-epoch
    host path — the evidence here is the epoch/admission structure and the
    per-epoch stats, not arena residency). ``pool.free`` is wired into the
    device session's row lifecycle: any array buffer a producer routes
    through the arena (e.g. auxiliary device-lowerable streams submitted
    alongside requests) has its row recycled when the buffer is freed.
    The device session defaults to ``plan_mode="loop"`` — the ready-queue
    epoch executor that advances each dependency frontier in one dispatch
    (DESIGN §2 A3); pass ``plan_mode="wave"``/``"frontier"`` to serve
    through the fixed-step table lowering instead.

    ``scheduler="mesh"`` serves through the mesh-sharded window
    (:class:`~..core.mesh_session.MeshDeviceSession`): the global
    admission plane places each request's chain on one shard (its slot
    buffer's RAW chain pins it there) while independent requests spread
    across shards/devices; ``n_shards`` defaults to the visible device
    count. Per-device slot accounting rides the pump: every iteration
    samples which shard owns each active slot (``shard_occupancy``), and
    the rolling ``shard_slot_samples`` trace plus the session's
    cross-shard/transfer counters land in the close report.

    **Cooperative preemption** (``preempt_rounds``, DESIGN §13): with
    the default ``None``, a request's whole decode chain is emitted at
    admission (the pre-QoS behavior). With ``preempt_rounds=k``, chains
    are emitted in segments of at most ``k`` decode rounds; at each
    segment boundary — an epoch boundary under the device/mesh
    schedulers, since a segment's tasks drain within one epoch — the
    chain either continues (next segment emitted from the retirement
    callback), finishes, or *yields its slot*: if a strictly more
    urgent admissible request is queued and no slot is free, the
    chain's opaque ``(cache, token, pos)`` state is parked on the
    Request, the slot is freed (stale-slot reset makes the handoff
    safe), and the request re-queues at its original age. Resume
    restores the parked state verbatim — no recompute, and the token
    stream is bit-identical to an unpreempted run. Each park increments
    ``Request.preemptions`` and the server-wide ``preemptions`` counter.
    """

    SCHEDULERS = ("frontier", "wave", "device", "mesh")

    def __init__(self, cfg: ArchConfig, params, *, max_slots: int = 4,
                 max_len: int = 64, window: int = 32, max_queue: int = 256,
                 scheduler: str = "frontier", max_inflight: int = 8,
                 history_limit: Optional[int] = 1024,
                 plan_mode: str = "loop", n_shards: Optional[int] = None,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 tenant_quota: Optional[Union[int, Dict[str, int]]] = None,
                 aging_s: Optional[float] = 5.0,
                 preempt_rounds: Optional[int] = None,
                 transfer_mode: str = "auto",
                 overlap_drains: bool = True):
        super().__init__(cfg, params, max_slots=max_slots, max_len=max_len,
                         max_queue=max_queue, history_limit=history_limit,
                         tenant_weights=tenant_weights,
                         tenant_quota=tenant_quota, aging_s=aging_s)
        if preempt_rounds is not None and preempt_rounds < 1:
            raise ValueError(
                f"preempt_rounds must be >= 1 or None, got {preempt_rounds}")
        self.preempt_rounds = preempt_rounds
        if scheduler == "frontier":
            from ..core.frontier import FrontierSession

            self.session = FrontierSession(window_size=window,
                                           max_inflight=max_inflight,
                                           max_group=1,
                                           history_limit=history_limit)
        elif scheduler == "wave":
            from ..core.session import WaveSession

            self.session = WaveSession(window_size=window,
                                       executor=SerialExecutor(),
                                       history_limit=history_limit)
        elif scheduler == "device":
            from ..core.device_dispatch import DeviceSession

            self.session = DeviceSession(window_size=window,
                                         plan_mode=plan_mode,
                                         history_limit=history_limit)
            # Row lifecycle wiring: freeing any pool buffer (per-request
            # prompts, auxiliary workload buffers) releases its arena row
            # for recycling — the device session's slabs stay bounded under
            # unbounded request streams.
            self.pool.add_free_hook(self.session.release_buffer)
        elif scheduler == "mesh":
            from ..core.mesh_session import MeshDeviceSession

            self.session = MeshDeviceSession(window_size=window,
                                             n_shards=n_shards,
                                             history_limit=history_limit,
                                             transfer_mode=transfer_mode,
                                             overlap_drains=overlap_drains)
            # One weight replica per shard device; each shard's serving
            # tasks run on its device against its own copy.
            self.session.replicate(self.weights)
            # Same row-lifecycle wiring as "device", fanned out to every
            # shard's arena (a freed buffer may hold rows on several).
            self.pool.add_free_hook(self.session.release_buffer)
        else:
            raise ValueError(
                f"session server scheduler must be one of {self.SCHEDULERS}, "
                f"got {scheduler!r}")
        self.scheduler_name = scheduler
        self._finished: List[Request] = []
        # set during close(): the flush retires chains (firing _finish_slot),
        # but a closing window must not receive fresh admissions
        self._closing = False
        # tid -> prefill | decode for tasks currently IN FLIGHT; entries
        # drop at retirement, so a long-lived server holds at most one
        # window's worth (schedule-kind traces for finished work live in
        # the rolling report_log, not here).
        self.task_kinds: Dict[int, str] = {}
        self.occupancy_samples: Deque[int] = collections.deque(
            maxlen=history_limit)
        # mesh only: rolling per-device slot-occupancy trace — one
        # {shard: active slot count} sample per pump plus one per request
        # retirement (bounded like every other monitoring surface —
        # soak-safe).
        self.shard_slot_samples: Deque[Dict[int, int]] = collections.deque(
            maxlen=history_limit)

    # -- retirement callbacks (fire inside session.poll/drive) --------------
    def _finish_slot(self, slot: int) -> None:
        if self.scheduler_name == "mesh":
            # sample while the finishing slot is still active: its chain
            # just executed, so shard attribution is known — the per-pump
            # sample can land when callback-admitted successors haven't
            # run yet (unattributed) or everything already drained
            self.shard_slot_samples.append(self.shard_occupancy())
        req = self._release_slot(slot)
        req.t_finish = time.perf_counter()
        self._finished.append(req)
        self._admit_ready()

    def _on_prefill_retired(self, task, buf_name: str, slot: int,
                            finish: bool) -> None:
        self.pool.free(buf_name)  # no leak
        self.task_kinds.pop(task.tid, None)
        if finish:  # zero decode rounds: the prefill IS the whole program
            self._finish_slot(slot)

    def _on_decode_retired(self, task, slot: int, boundary: bool) -> None:
        self.task_kinds.pop(task.tid, None)
        req = self.active[slot]
        _, tok, _ = self.slots[slot].value
        req.generated.append(int(np.asarray(tok)[0]))
        req.rounds_left -= 1
        if not boundary:
            return
        # Segment boundary: finish, yield the slot, or emit the next
        # segment (the continuation submits from inside the retirement
        # callback — the session RLock permits it, and the tasks land in
        # the window for the next epoch/group).
        if req.rounds_left <= 0:
            self._finish_slot(slot)
        elif self._should_yield(req):
            self._park(slot)
        else:
            self._emit_decode_segment(req)

    def _should_yield(self, req: Request) -> bool:
        """Cooperative-preemption test at a segment boundary: yield iff
        strictly more urgent work exists — RUNNING in another slot (the
        urgent class takes every host round-trip until it drains:
        priority isolation, not just a slot), or admissible in the queue
        with no free slot to serve it. Equal urgency never preempts (no
        thrash between peers, and aging re-levels a parked chain so
        isolation is starvation-bounded), and quota-blocked waiters don't
        trigger a park they couldn't use."""
        if self.preempt_rounds is None:
            return False
        now = time.perf_counter()
        mine = self.effective_priority(req, now)
        for r in self.active.values():
            if r is not req and self.effective_priority(r, now) < mine:
                return True
        if self.free or not self.queue:
            return False
        for r in self.queue:
            quota = self._quota_of(r.tenant)
            if quota is not None and self._tenant_active.get(r.tenant, 0) >= quota:
                continue
            if self.effective_priority(r, now) < mine:
                return True
        return False

    def _park(self, slot: int) -> None:
        """Preempt: capture the chain's opaque slot state (fresh — its
        segment's last decode just retired), free the slot, and re-queue
        the request at its original age (rid order; the internal
        re-queue is exempt from the admission bound — the request was
        already admitted once). Resume happens through the normal
        admission path via ``parked_state``."""
        req = self._release_slot(slot)
        req.parked_state = self.slots[slot].value
        req.slot = None
        req.preemptions += 1
        self.preemptions += 1
        self.queue.append(req)
        self._admit_ready()

    # -- service loop --------------------------------------------------------
    def _admit_ready(self) -> None:
        """Admission sweep: grant free slots to queued requests in QoS
        order. Runs between pumps AND from the slot-freeing retirement
        callbacks (finish, park). The callback path matters: the
        session's poll/drive pumps staged work to quiescence, and under
        lazy segment emission a long chain's rounds cascade entirely
        inside one drive — a slot freed mid-cascade would sit idle until
        the cascade drains, so an urgent arrival that parked a flood
        chain would still wait behind the rest of the epoch. Admitting
        from inside the callback lets the successor's program join the
        same cascade (submission from retirement callbacks is the same
        contract the decode continuations rely on)."""
        if self._closing or self.session.closed:
            return
        while self.queue and self.free:
            req = self._pick_next()
            if req is None:  # everything queued is quota-blocked/held back
                break
            self._admit(req)

    def _admit(self, req: Request) -> None:
        """Emit the request's kernel program into the live window at
        admission: the prefill plus its decode chain — whole
        (``preempt_rounds=None``: termination is count-based, so the full
        chain is known up front and no mid-request host round-trip ever
        gates it, §III-D) or in preemptible segments. The window
        serializes the chain via the slot buffer's RAW hazards and
        co-schedules it against other slots' chains (disjoint buffers);
        the per-request stream stamps each task with the request's
        effective priority bucket so urgent chains launch first among
        independent READY kernels. A resumed request (parked state
        restored by ``_grant_slot``) skips the prefill and emits only its
        remaining rounds."""
        tok_buf = self._grant_slot(req)
        s = req.slot
        if tok_buf is None:  # resuming a preempted chain
            self._emit_decode_segment(req)
            return
        stream = self._stream_for(req)
        task = self._prefill_kernel.launch(
            stream, inputs=(self.slots[s], self.weights, tok_buf),
            outputs=(self.slots[s],))
        self.task_kinds[task.tid] = "prefill"
        # Decode rounds the cache can actually hold: zero when max_new=0 or
        # the prompt already fills it — never force a phantom round that
        # would advance pos past max_len (the old max(1, ...) clamp).
        req.rounds_left = min(req.max_new, self.max_len - 1 - len(req.prompt))
        self.session.on_task_retired(
            task, lambda t, n=tok_buf.name, s=s, fin=(req.rounds_left == 0):
            self._on_prefill_retired(t, n, s, fin))
        self._emit_decode_segment(req, stream)

    def _stream_for(self, req: Request) -> TaskStream:
        """Live per-request stream: AcsKernel.launch feeds the session's
        window directly, tagged for per-request accounting and stamped
        with the request's current effective priority bucket."""
        return TaskStream(sink=self.session, tag=f"req{req.rid}",
                          record=False,
                          priority=self.effective_priority(req))

    def _emit_decode_segment(self, req: Request,
                             stream: Optional[TaskStream] = None) -> None:
        """Emit the next run of decode rounds for the request's chain:
        everything left when ``preempt_rounds`` is None, else at most
        ``preempt_rounds`` rounds — the boundary round's retirement
        callback then decides finish / yield / continue."""
        if req.rounds_left <= 0:
            return
        s = req.slot
        if stream is None:
            stream = self._stream_for(req)
        seg = (req.rounds_left if self.preempt_rounds is None
               else min(req.rounds_left, self.preempt_rounds))
        bufs = (self.slots[s],)
        for k in range(seg):
            dtask = self._decode_kernel.launch(
                stream, inputs=(self.weights,) + bufs, outputs=bufs)
            self.task_kinds[dtask.tid] = "decode"
            self.session.on_task_retired(
                dtask,
                lambda t, s=s, boundary=(k == seg - 1):
                self._on_decode_retired(t, s, boundary))

    def pump(self) -> List[Request]:
        """One non-blocking service iteration; returns newly finished
        requests. Producers may call ``submit`` at any time between pumps
        (or from another thread with a threaded session). Safe after
        ``close()``: it then only drains requests that finished during the
        closing flush."""
        if not self.session.closed:
            self.session.poll()
            self._admit_ready()
            self.occupancy_samples.append(self.session.window.resident())
            if self.scheduler_name == "mesh":
                self.shard_slot_samples.append(self.shard_occupancy())
        out, self._finished = self._finished, []
        return out

    def shard_occupancy(self) -> Dict[int, int]:
        """Per-device slot accounting (mesh scheduler): how many ACTIVE
        request slots each shard currently owns — a slot is attributed to
        the shard that last wrote its buffer, i.e. where its chain runs.
        Slots whose chain has not executed yet are not attributed."""
        counts: Dict[int, int] = {}
        shard_of = getattr(self.session, "shard_of", None)
        if shard_of is None:
            return counts
        for s in self.active:
            shard = shard_of(self.slots[s])
            if shard is not None:
                counts[shard] = counts.get(shard, 0) + 1
        return counts

    def run_until_drained(self, max_iters: int = 10_000) -> List[Request]:
        """Serve until queue and slots empty (blocking between pumps only
        when nothing retired — the session's oldest-group sync). Raises
        :class:`DrainTimeout` (with the stuck queue/active counts and the
        requests that DID finish) when ``max_iters`` pumps don't drain
        the server — it used to return the partial list silently."""
        out: List[Request] = []
        for _ in range(max_iters):
            done = self.pump()
            out.extend(done)
            if not self.queue and not self.active:
                return out
            if not done:
                self.session.drive()
        raise DrainTimeout(
            f"run_until_drained: {max_iters} pumps left "
            f"{len(self.queue)} queued / {len(self.active)} active requests",
            queue_depth=len(self.queue), active_slots=len(self.active),
            finished=out)

    def close(self):
        """Close the underlying session and log its final report. Chains
        still in flight retire during the closing flush — collect those
        requests with one more ``pump()`` after close. Under
        ``preempt_rounds`` the continuation segments of in-flight chains
        are emitted lazily from retirement callbacks, which cannot feed a
        closing window — so drain first (finished requests stay
        collectable via ``pump()``)."""
        if self.preempt_rounds is not None and (self.queue or self.active):
            # two statements on purpose: pump() REBINDS self._finished, so
            # the attribute must be read after run_until_drained returns
            drained = self.run_until_drained()
            self._finished.extend(drained)
        self._closing = True
        report = self.session.close()
        entry = report.as_dict()
        entry["preemptions"] = self.preemptions
        entry["occupancy_mean"] = (
            float(np.mean(self.occupancy_samples)) if self.occupancy_samples else 0.0)
        if hasattr(report, "session_stats"):  # device session epoch counters
            entry["device_session"] = dict(report.session_stats)
        if self.shard_slot_samples:  # mesh per-device slot accounting
            shards: Dict[int, List[int]] = {}
            for sample in self.shard_slot_samples:
                for shard, n in sample.items():
                    shards.setdefault(shard, []).append(n)
            entry["shard_slots_mean"] = {
                str(shard): float(np.mean(v)) for shard, v in sorted(shards.items())}
        if self.scheduler_name == "mesh":
            # Transfer-plane summary at top level (the full per-shard audit
            # stays under device_session): which link mode the session
            # selected, how traffic split d2d vs staged, and the max
            # concurrent in-flight shards the overlapped drain reached.
            stats = self.session.session_stats()
            for key in ("transfer_mode", "d2d_moves", "staged_moves",
                        "d2d_fallbacks", "drain_overlap", "overlap_drains"):
                entry[key] = stats[key]
        self.report_log.append(entry)
        return report
