"""Live-fed scheduler sessions (DESIGN.md §10): interleaved submit/poll
must be observationally equivalent to the serial baseline for every
session policy; the window's open/drain semantics must distinguish "empty
but session open" from "closed and complete"; and window size 1 must
degenerate to serial even under live feeding.

Streams are generated like test_scheduler_equivalence: random reads/writes
over a shared pool with non-commutative arithmetic, so any illegal reorder
changes the result.
"""

import numpy as np
import pytest
from _prophelper import given, settings, st

import jax.numpy as jnp

from repro.core import (
    BufferPool,
    SESSION_NAMES,
    SchedulingWindow,
    Task,
    TaskStream,
    make_session,
    run_serial,
)
from repro.core.task import default_segments
from repro.core.wrapper import AcsKernel

D = 4


def _axpy(x, y):
    return 1.5 * x + y + 1.0


def _mul(x, y):
    return x * y - 0.5


def _neg(x, y):
    return -x + 0.25 * y


OPS = {"axpy": _axpy, "mul": _mul, "neg": _neg}


def build_stream(seed: int, n_tasks: int, n_buffers: int):
    rng = np.random.RandomState(seed)
    pool = BufferPool()
    buffers = [
        pool.alloc((D,), np.float32, value=jnp.asarray(rng.randn(D).astype(np.float32)))
        for _ in range(n_buffers)
    ]
    tasks = []
    names = list(OPS)
    for _ in range(n_tasks):
        op = names[rng.randint(len(names))]
        i0, i1 = rng.randint(n_buffers), rng.randint(n_buffers)
        o = rng.randint(n_buffers)
        ins = (buffers[i0], buffers[i1])
        outs = (buffers[o],)
        r, w = default_segments(ins, outs)
        tasks.append(
            Task(opcode=op, fn=OPS[op], inputs=ins, outputs=outs,
                 read_segments=r, write_segments=w)
        )
    return pool, buffers, tasks


def final_values(buffers):
    return np.stack([np.asarray(b.value) for b in buffers])


def serial_ref(seed, n_tasks=30, n_buffers=6):
    _, buffers, tasks = build_stream(seed, n_tasks, n_buffers)
    run_serial(tasks)
    return final_values(buffers)


def feed_interleaved(session, tasks, seed, poll_prob=0.7):
    """Submit in random-sized chunks with polls in between — the live-FIFO
    pattern of paper §III-D."""
    rng = np.random.RandomState(seed)
    i = 0
    while i < len(tasks):
        k = 1 + rng.randint(5)
        session.submit(tasks[i : i + k])
        i += k
        if rng.rand() < poll_prob:
            session.poll()
    return session.close()


class TestInterleavedEquivalence:
    @pytest.mark.parametrize("kind", SESSION_NAMES)
    @pytest.mark.parametrize("seed", [0, 1])
    def test_matches_serial(self, kind, seed):
        ref = serial_ref(seed)
        _, buffers, tasks = build_stream(seed, 30, 6)
        report = feed_interleaved(make_session(kind, window_size=8), tasks, seed)
        np.testing.assert_allclose(final_values(buffers), ref, rtol=1e-6)
        assert report.window_stats["retired"] == 30
        assert sum(len(w) for w in report.waves) == 30

    @given(st.integers(0, 10_000), st.integers(1, 17))
    @settings(max_examples=10, deadline=None)
    def test_property_any_seed_any_window(self, seed, window):
        ref = serial_ref(seed, n_tasks=18, n_buffers=5)
        _, buffers, tasks = build_stream(seed, 18, 5)
        feed_interleaved(make_session("wave", window_size=window), tasks, seed)
        np.testing.assert_allclose(final_values(buffers), ref, rtol=1e-6)

    def test_window_one_live_feed_degenerates_to_serial(self):
        ref = serial_ref(3)
        _, buffers, tasks = build_stream(3, 30, 6)
        report = feed_interleaved(make_session("wave", window_size=1), tasks, 3)
        np.testing.assert_allclose(final_values(buffers), ref, rtol=1e-6)
        assert all(len(w) == 1 for w in report.waves)
        assert [w[0] for w in report.waves] == [t.tid for t in tasks]  # program order

    def test_threaded_idle_workers_wake_on_late_submission(self):
        """Workers parked on the condition variable (no spin) must pick up
        work submitted long after the window went idle."""
        ref = serial_ref(5)
        _, buffers, tasks = build_stream(5, 30, 6)
        s = make_session("threaded", window_size=8, num_streams=3)
        s.submit(tasks[:10])
        s.flush()  # window idles; workers park
        assert s.outstanding == 0 and not s.window.drained()
        s.submit(tasks[10:])
        report = s.close()
        np.testing.assert_allclose(final_values(buffers), ref, rtol=1e-6)
        assert report.exec_stats["dispatches"] == 30

    def test_frontier_executor_rejects_second_live_session(self):
        """One live session per executor: opening a session over a ledger
        holding another session's in-flight groups must fail loudly, not
        steal (and mis-retire) those groups."""
        from repro.core import FrontierSession, GroupExecutor

        ex = GroupExecutor()
        pool = BufferPool()
        a = pool.alloc((D,), np.float32, value=jnp.ones(D))
        b = pool.alloc((D,), np.float32, value=jnp.zeros(D))
        r, w = default_segments((a, a), (b,))
        task = Task(opcode="axpy", fn=_axpy, inputs=(a, a), outputs=(b,),
                    read_segments=r, write_segments=w)
        ex.launch([task])  # group now on the in-flight ledger
        with pytest.raises(RuntimeError):
            FrontierSession(executor=ex)
        ex.sync_oldest()  # drained ledger: a new session may open
        FrontierSession(executor=ex)

    def test_frontier_inflight_survives_submissions(self):
        """Groups launched before a submission retire normally after it —
        the executor's in-flight ledger is session-lifetime state."""
        ref = serial_ref(7)
        _, buffers, tasks = build_stream(7, 30, 6)
        s = make_session("frontier", window_size=8, max_inflight=4)
        s.submit(tasks[:12])
        s.poll()  # stages groups
        s.poll()  # launches: groups now in flight
        s.submit(tasks[12:])  # feed while in flight
        report = s.close()
        np.testing.assert_allclose(final_values(buffers), ref, rtol=1e-6)
        assert sum(len(g.tids) for g in report.groups) == 30


class TestDrainedVsClosed:
    def test_open_empty_is_idle_not_drained(self):
        w = SchedulingWindow(4)
        assert w.drained()  # batch default: input closed from birth
        w.open_input()
        assert w.idle() and not w.drained()
        w.close_input()
        assert w.drained()

    def test_live_window_with_work_is_neither(self):
        _, _, tasks = build_stream(0, 3, 3)
        w = SchedulingWindow(4)
        w.open_input()
        w.submit(tasks[0])
        assert not w.idle() and not w.drained()
        t = w.ready_tasks()[0]
        w.mark_executing(t)
        w.retire(t)
        assert w.idle() and not w.drained()
        w.close_input()
        assert w.drained()

    def test_submit_after_close_raises(self):
        _, _, tasks = build_stream(0, 2, 2)
        s = make_session("wave", window_size=4)
        s.submit(tasks[0])
        s.close()
        with pytest.raises(RuntimeError):
            s.submit(tasks[1])
        with pytest.raises(RuntimeError):
            s.close()  # double close


class TestRetirementObservation:
    def test_callbacks_fire_once_per_task_in_retire_order(self):
        _, _, tasks = build_stream(2, 12, 4)
        s = make_session("serial")
        seen = []
        s.submit(tasks, on_retire=lambda t: seen.append(t.tid))
        s.close()
        assert seen == [t.tid for t in tasks]  # serial: program order, once each

    def test_ticket_and_late_callback(self):
        _, _, tasks = build_stream(2, 4, 3)
        s = make_session("wave", window_size=4)
        s.submit(tasks)
        tk = s.ticket(tasks[0])
        assert not tk.done()
        s.flush()
        assert tk.done()
        late = []
        s.on_task_retired(tasks[1], lambda t: late.append(t.tid))  # already retired
        assert late == [tasks[1].tid]
        s.close()

    def test_submit_reports_backlog_depth(self):
        pool = BufferPool()
        ins = [pool.alloc((D,), np.float32, value=jnp.ones(D)) for _ in range(5)]
        outs = [pool.alloc((D,), np.float32, value=jnp.zeros(D)) for _ in range(5)]
        tasks = []
        for i in range(5):
            r, w = default_segments((ins[i], ins[i]), (outs[i],))
            tasks.append(Task(opcode="axpy", fn=_axpy, inputs=(ins[i], ins[i]),
                              outputs=(outs[i],), read_segments=r, write_segments=w))
        s = make_session("wave", window_size=2)
        depth = s.submit(tasks)  # 2 resident + 3 queued in the input FIFO
        assert depth == 5
        assert s.backlog() == 5
        assert s.window.fifo_depth() == 3
        s.close()


class TestLiveTaskStream:
    def test_sink_feeds_session_and_tags_tasks(self):
        """AcsKernel.launch into a sink-ed stream lands in the live window
        immediately — the wrapper-to-window path of Fig 16/17, open-loop."""
        s = make_session("wave", window_size=4)
        pool = BufferPool()
        a = pool.alloc((D,), np.float32, value=jnp.ones(D))
        b = pool.alloc((D,), np.float32, value=jnp.zeros(D))
        stream = TaskStream(sink=s, tag="tenant0")
        kern = AcsKernel(name="axpy_live_test", fn=_axpy)
        task = kern.launch(stream, inputs=(a, a), outputs=(b,))
        assert s.backlog() == 1  # submitted by push, no explicit submit call
        assert task.stream_tag == "tenant0"
        s.close()
        assert s.retired_by_tag == {"tenant0": 1}
        np.testing.assert_allclose(np.asarray(b.value), 1.5 + 1.0 + 1.0)

    def test_bad_sink_rejected(self):
        with pytest.raises(TypeError):
            TaskStream(sink=object())


class TestDeviceSessionObservation:
    """The persistent device window keeps values device-resident between
    epochs; retirement observers must still see host-fresh values."""

    def _one_task(self):
        pool = BufferPool()
        x = pool.alloc((D,), np.float32, value=jnp.ones(D))
        y = pool.alloc((D,), np.float32, value=jnp.zeros(D))
        r, w = default_segments((x, x), (y,))
        task = Task(opcode="axpy", fn=_axpy, inputs=(x, x), outputs=(y,),
                    read_segments=r, write_segments=w)
        return y, task

    def test_ticket_holder_observes_fresh_value_at_poll(self):
        """Regression: a ticketed task is a retirement observer — its
        output must be synced back before the ticket fires, exactly like
        callback watchers."""
        y, task = self._one_task()
        s = make_session("device", window_size=4)
        s.submit(task)
        tk = s.ticket(task)
        s.poll()
        assert tk.done()
        np.testing.assert_allclose(np.asarray(y.value), 1.5 + 1.0 + 1.0)
        s.close()

    def test_late_observers_also_see_fresh_values(self):
        """Regression: observers registered AFTER an unwatched epoch
        retired the task (the fire-immediately paths) must sync first —
        a late callback or ticket reads the same values an early one
        would."""
        y, task = self._one_task()
        s = make_session("device", window_size=4)
        s.submit(task)
        s.poll()  # unwatched epoch: sync deferred
        seen = []
        s.on_task_retired(task, lambda t: seen.append(np.asarray(y.value).copy()))
        assert len(seen) == 1
        np.testing.assert_allclose(seen[0], 1.5 + 1.0 + 1.0)
        tk = s.ticket(task)
        assert tk.done()
        np.testing.assert_allclose(np.asarray(y.value), 1.5 + 1.0 + 1.0)
        s.close()

    def test_unwatched_values_require_sync(self):
        """Documented contract: without an observer, an epoch defers the
        host sync; ``sync()`` (or flush/close) makes direct reads safe."""
        y, task = self._one_task()
        s = make_session("device", window_size=4)
        s.submit(task)
        s.poll()
        assert s.session_stats()["host_syncs"] == 0  # deferred
        s.sync()
        assert s.session_stats()["host_syncs"] == 1
        np.testing.assert_allclose(np.asarray(y.value), 1.5 + 1.0 + 1.0)
        s.close()

    def test_runner_session_shares_registry(self):
        """DeviceWindowRunner.session() mirrors the other schedulers'
        session() handles: same opcode registry, fresh per-session arena,
        serial-equivalent results."""
        from repro.core import DeviceWindowRunner

        ref = serial_ref(4)
        _, buffers, tasks = build_stream(4, 30, 6)
        runner = DeviceWindowRunner(window_size=8, plan_mode="frontier")
        s = runner.session()
        assert s.registry is runner.registry
        assert s.plan_mode == "frontier"
        report = feed_interleaved(s, tasks, 4)
        np.testing.assert_allclose(final_values(buffers), ref, rtol=1e-6)
        assert report.window_stats["retired"] == 30


class TestDeviceSessionReadBack:
    """Host read-backs (flush, sync) hand out NumPy values, one transfer
    per touched class; the in-epoch host path keeps its inputs on the
    device."""

    @pytest.mark.parametrize("plan_mode", ["wave", "loop"])
    def test_flush_of_a_sim_gives_host_values_equal_to_serial(self,
                                                              plan_mode):
        from repro.core import DeviceSession
        from repro.sim import ENVIRONMENTS, PhysicsEngine

        ref, dev = (PhysicsEngine(ENVIRONMENTS["cheetah"], n_envs=4,
                                  group_size=2, seed=0) for _ in range(2))
        s = DeviceSession(plan_mode=plan_mode)
        for step in range(3):
            stream = TaskStream()
            ref.emit_step(stream)
            run_serial(stream.tasks)
            stream = TaskStream()
            dev.emit_step(stream)
            s.submit(stream.tasks)
            before = s.session_stats()
            s.flush()
            after = s.session_stats()
            assert after["host_syncs_d2h"] - before["host_syncs_d2h"] == 1
            moved = after["unpack_transfers"] - before["unpack_transfers"]
            assert 0 < moved <= after["n_classes"]
            for g in dev.groups:
                assert isinstance(g.state.value, np.ndarray)
            np.testing.assert_array_equal(dev.state_snapshot(),
                                          ref.state_snapshot())
        s.close()

    def test_host_path_task_reads_device_resident_inputs(self):
        """A device step, then a host-path task (an opaque operand) that
        reads its output in the same epoch: the input is read back as a
        device slice, so the task gets a jax.Array and no upload follows."""
        import jax

        from repro.core import DeviceSession

        pool = BufferPool()
        x = pool.alloc((D,), np.float32, value=jnp.ones(D))
        y = pool.alloc((D,), np.float32, value=jnp.zeros(D))
        z = pool.alloc((D,), np.float32, value=jnp.zeros(D))
        scale = pool.alloc((1,), np.float32, value=(jnp.full(D, 2.0),))
        tasks = []
        for fn, ins, outs in ((_axpy, (x, x), (y,)),
                              (lambda v, p: v * p[0], (y, scale), (z,))):
            r, w = default_segments(ins, outs)
            tasks.append(Task(opcode=f"op{len(tasks)}", fn=fn, inputs=ins,
                              outputs=outs, read_segments=r,
                              write_segments=w))
        s = DeviceSession(window_size=4)
        seen = []
        run_task = s._host_exec.run_task

        def spy(task, values):
            seen.append(values)
            return run_task(task, values)

        s._host_exec.run_task = spy
        s.submit(tasks)
        s.poll()
        stats = s.session_stats()
        assert stats["device_dispatches"] == 1
        assert stats["host_task_dispatches"] == 1
        assert stats["host_syncs_d2h"] == 1 and stats["host_syncs_h2d"] == 0
        assert stats["unpack_transfers"] == 0
        (values,) = seen
        assert isinstance(values[0], jax.Array)
        assert isinstance(y.value, jax.Array)
        s.close()
        np.testing.assert_array_equal(np.asarray(z.value),
                                      np.full(D, 7.0, np.float32))


class TestBufferPoolFree:
    def test_free_releases_name_without_recycling_addresses(self):
        pool = BufferPool()
        a = pool.alloc((D,), np.float32, name="x", value=jnp.ones(D))
        pool.free("x")
        assert "x" not in pool
        b = pool.alloc((D,), np.float32, name="x", value=jnp.ones(D))
        assert b.base > a.base  # bump pointer stays monotone
        with pytest.raises(KeyError):
            pool.free("never-allocated")

    def test_free_hooks_fire_with_the_buffer(self):
        pool = BufferPool()
        seen = []
        pool.add_free_hook(seen.append)
        b = pool.alloc((D,), np.float32, name="hooked", value=jnp.ones(D))
        pool.free("hooked")
        assert seen == [b]


class TestHistoryLimit:
    """Bounded session-lifetime bookkeeping (history_limit=N): schedule
    traces rotate, yet retirement observation stays exact for every tid
    ever retired."""

    def test_traces_rotate_but_counters_stay_exact(self):
        _, buffers, tasks = build_stream(11, 30, 6)
        s = make_session("wave", window_size=4, history_limit=5)
        for t in tasks:
            s.submit(t)
            s.poll()
        report = s.close()
        assert len(s.waves) <= 5
        assert report.window_stats["retired"] == 30
        np.testing.assert_allclose(final_values(buffers), serial_ref(11),
                                   rtol=1e-6)

    def test_fire_immediately_survives_tid_eviction(self):
        """A callback/ticket registered long after retirement must still
        fire immediately even when the tid was rotated out of the live
        retired set into the evicted intervals."""
        _, _, tasks = build_stream(12, 40, 6)
        s = make_session("wave", window_size=4, history_limit=4)
        for t in tasks:
            s.submit(t)
            s.poll()
        assert len(s._retired_tids) <= 4  # rotated
        fired = []
        s.on_task_retired(tasks[0], lambda t: fired.append(t.tid))
        assert fired == [tasks[0].tid]
        assert s.ticket(tasks[0]).done()
        for t in tasks:  # exact membership for every tid ever retired
            assert s._is_retired(t.tid)
        unseen = Task(opcode="axpy", fn=_axpy, inputs=(), outputs=(),
                      read_segments=(), write_segments=())
        assert not s._is_retired(unseen.tid)
        s.close()

    def test_evicted_intervals_stay_merged(self):
        """Monotone tid eviction collapses into O(1) intervals, not one
        entry per evicted tid."""
        _, _, tasks = build_stream(13, 50, 6)
        s = make_session("wave", window_size=4, history_limit=4)
        for t in tasks:
            s.submit(t)
            s.poll()
        assert len(s._retired_evicted) <= 2
        s.close()

    def test_rejects_nonpositive_limit(self):
        with pytest.raises(ValueError, match="history_limit"):
            make_session("wave", history_limit=0)

    def test_device_session_epoch_log_rotates(self):
        s = make_session("device", window_size=4, history_limit=3)
        for seed in range(5):
            _, _, tasks = build_stream(seed, 4, 3)
            s.submit(tasks)
            s.poll()
        assert len(s.epoch_log) <= 3
        assert s.session_stats()["epochs"] == 5
        s.close()


class TestDeviceSessionRecycling:
    """Arena row lifecycle through the live device session: release feeds
    the free-list, recurring traffic recycles rows (bounded slabs, plan
    cache hits stay valid), and compaction invalidates exactly the moved
    structure keys."""

    def _phase(self, session, pool, n=4, value=1.0):
        """One request-like burst: fresh buffers, a 2-task chain, flush to
        retire; returns the buffers (caller releases them)."""
        bufs = [pool.alloc((D,), np.float32, value=jnp.full(D, value + i))
                for i in range(n)]
        chain = []
        for src, dst in ((0, 2), (2, 3)):
            r, w = default_segments((bufs[src], bufs[1]), (bufs[dst],))
            chain.append(Task(opcode="axpy", fn=_axpy,
                              inputs=(bufs[src], bufs[1]),
                              outputs=(bufs[dst],),
                              read_segments=r, write_segments=w))
        session.submit(chain)
        session.flush()
        return bufs

    def test_release_bounds_rows_and_cache_under_recurring_traffic(self):
        from repro.core import DeviceSession

        s = DeviceSession(window_size=8)
        pool = BufferPool()
        rows_after = []
        for phase in range(8):
            bufs = self._phase(s, pool, value=float(phase))
            for b in bufs:
                assert s.release_buffer(b)
            rows_after.append(sum(len(s.arena.rows(c))
                                  for c in range(s.arena.n_classes())))
        stats = s.session_stats()
        # slab never grows past the first phase's footprint
        assert rows_after[-1] == rows_after[0]
        assert stats["arena_recycled_rows"] > 0
        assert stats["slab_bytes"] == rows_after[0] * 8 * 4  # padded rows
        # recycled rows repeat structure keys: the cache stays bounded and
        # hot instead of growing one entry per phase
        assert stats["plan_cache_entries"] <= 2
        assert stats["plan_cache_hits"] >= 5
        s.close()

    def test_without_release_rows_grow_monotonically(self):
        """The pre-fix behavior, kept as the contrast leg: no release, one
        leaked row per buffer per phase."""
        from repro.core import DeviceSession

        s = DeviceSession(window_size=8)
        pool = BufferPool()
        for phase in range(4):
            self._phase(s, pool, value=float(phase))
        assert s.arena.live_rows() == 4 * 4
        assert s.session_stats()["plan_cache_entries"] == 4
        s.close()

    def test_compaction_invalidates_exactly_moved_classes(self):
        """Two shape classes; compacting one must drop only ITS cached
        plans — the other class's entry survives and keeps hitting — and
        surviving values stay bit-exact (device-side gather)."""
        from repro.core import DeviceSession

        s = DeviceSession(window_size=8, compact_min_rows=8,
                          compact_waste=0.5)
        pool = BufferPool()
        # class A: (D,) rows
        a = [pool.alloc((D,), np.float32, value=jnp.full(D, 1.0 + i))
             for i in range(8)]
        # class B: (2, D) rows — a distinct padded shape class
        b = [pool.alloc((2, D), np.float32, value=jnp.full((2, D), 50.0 + i))
             for i in range(2)]

        def task_over(ins, outs):
            r, w = default_segments(ins, outs)
            return Task(opcode="axpy", fn=_axpy, inputs=ins, outputs=outs,
                        read_segments=r, write_segments=w)

        # epoch 1: class-A-only plan touching all 8 A rows (pairwise)
        s.submit([task_over((a[i], a[i + 1]), (a[i + 1],))
                  for i in range(0, 8, 2)])
        s.flush()
        s.submit(task_over((b[0], b[1]), (b[1],)))
        s.flush()  # epoch 2: class-B-only plan
        keys_before = set(s._plan_cache.keys())
        assert len(keys_before) == 2
        # kill 6 of 8 class-A rows -> waste 6/8 >= 0.5; class B untouched
        for buf in a[2:]:
            assert s.release_buffer(buf)
        # next epoch compacts class A first, then executes
        s.submit(task_over((b[0], b[1]), (b[1],)))  # same B structure
        s.flush()
        stats = s.session_stats()
        assert stats["arena_compactions"] == 1
        assert stats["arena_generation"] == 1
        assert stats["plan_cache_invalidations"] == 1  # the class-A entry
        surviving = keys_before & set(s._plan_cache.keys())
        assert len(surviving) == 1  # class-B entry survived...
        assert stats["plan_cache_hits"] >= 1  # ...and kept hitting
        # values across the compaction stay bit-exact
        s.sync()
        np.testing.assert_array_equal(
            np.asarray(a[1].value),
            np.asarray(_axpy(jnp.full(D, 1.0), jnp.full(D, 2.0))))
        expected_b1 = _axpy(jnp.full((2, D), 50.0),
                            _axpy(jnp.full((2, D), 50.0),
                                  jnp.full((2, D), 51.0)))
        np.testing.assert_array_equal(np.asarray(b[1].value),
                                      np.asarray(expected_b1))
        s.close()

    def test_plan_cache_lru_cap(self):
        from repro.core import DeviceSession

        s = DeviceSession(window_size=8, plan_cache_limit=2)
        pool = BufferPool()
        bufs = [pool.alloc((D,), np.float32, value=jnp.ones(D))
                for _ in range(6)]
        # three structurally distinct single-task epochs
        for ins, outs in (((bufs[0], bufs[1]), (bufs[1],)),
                          ((bufs[2], bufs[3]), (bufs[3],)),
                          ((bufs[4], bufs[5]), (bufs[5],))):
            r, w = default_segments(ins, outs)
            s.submit(Task(opcode="axpy", fn=_axpy, inputs=ins, outputs=outs,
                          read_segments=r, write_segments=w))
            s.poll()
        stats = s.session_stats()
        assert stats["plan_cache_entries"] == 2
        assert stats["plan_cache_evictions"] == 1
        s.close()
