"""ACS-HW analogue: device-resident window interpreter (DESIGN.md §2 A3).

Equivalence with the serial baseline + the single-dispatch property that is
the whole point of moving the window onto the device. The arena path
(mixed shape classes, real workloads) is covered in test_arena.py; this
module keeps the toy universe honest — including the legacy uniform-slab
interpreter and its (now loud) arity limit.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    BufferPool,
    DeviceOpRegistry,
    DeviceWindowRunner,
    Task,
    plan_frontier,
    plan_waves,
    run_serial,
)
from repro.core.device_dispatch import MAX_ARITY, compile_wave_plan
from repro.core.task import default_segments

D = 8


def _axpy(x, y, z):
    return 1.5 * x + y + 1.0


def _mul(x, y, z):
    return x * y - 0.5


OPS = {"axpy": _axpy, "mul": _mul}


def build(seed, n_tasks, n_buffers):
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
        ins = (buffers[rng.randint(n_buffers)], buffers[rng.randint(n_buffers)])
        outs = (buffers[rng.randint(n_buffers)],)
        r, w = default_segments(ins, outs)
        # legacy interpreter fns take (x, y, z); serial fn must match arity 2
        fn2 = (lambda f: lambda x, y: f(x, y, None))(OPS[op])
        tasks.append(
            Task(opcode=op, fn=fn2, inputs=ins, outputs=outs, read_segments=r, write_segments=w)
        )
    return pool, buffers, tasks


@pytest.fixture(scope="module")
def registry():
    reg = DeviceOpRegistry()
    for name, fn in OPS.items():
        reg.register(name, fn)
    return reg


class TestDeviceWindowRunner:
    @pytest.mark.parametrize("seed", [0, 1, 5])
    def test_matches_serial(self, registry, seed):
        _, ref_bufs, ref_tasks = build(seed, 30, 6)
        run_serial(ref_tasks)
        ref = np.stack([np.asarray(b.value) for b in ref_bufs])

        _, dev_bufs, dev_tasks = build(seed, 30, 6)
        runner = DeviceWindowRunner(registry, window_size=16)
        report = runner.execute(dev_tasks, dev_bufs)
        got = np.stack([np.asarray(b.value) for b in dev_bufs])

        np.testing.assert_array_equal(got, ref)
        assert report.exec_stats["dispatches"] == 1  # whole stream, one launch

    def test_single_dispatch_vs_serial_dispatch_count(self, registry):
        _, bufs, tasks = build(2, 50, 8)
        runner = DeviceWindowRunner(registry, window_size=32)
        report = runner.execute(tasks, bufs)
        assert report.exec_stats["dispatches"] == 1
        assert report.exec_stats["tasks_run"] == 50

    def test_compiled_plan_reused_across_inputs(self, registry):
        """Same lowered-program structure across different inputs => no
        recompilation: the CUDA-Graph-without-reconstruction property (A2)."""
        runner = DeviceWindowRunner(registry, window_size=16)
        for shift in (0.0, 1.0):  # same stream structure, different values
            _, bufs, tasks = build(0, 30, 6)
            for b in bufs:
                b.value = b.value + shift
            runner.execute(tasks, bufs)
        assert len(runner._compiled) == 1

    def test_window_stats_come_from_planning_pass(self, registry):
        """The report's window stats are the planning window's real
        counters, not a fresh all-zero container (seed bug)."""
        _, bufs, tasks = build(4, 25, 5)
        report = DeviceWindowRunner(registry, window_size=8).execute(tasks, bufs)
        assert report.window_stats["inserted"] == 25
        assert report.window_stats["retired"] == 25
        assert report.window_stats["dep_checks"] > 0
        assert 1 <= report.window_stats["max_resident"] <= 8

    def test_strict_registry_rejects_unknown_opcode(self):
        reg = DeviceOpRegistry()  # strict, nothing registered
        _, bufs, tasks = build(0, 5, 3)
        with pytest.raises(KeyError, match="not in the device registry"):
            DeviceWindowRunner(reg).execute(tasks, bufs)

    def test_auto_registry_accepts_any_opcode(self):
        _, ref_bufs, ref_tasks = build(6, 20, 5)
        run_serial(ref_tasks)
        ref = np.stack([np.asarray(b.value) for b in ref_bufs])
        _, bufs, tasks = build(6, 20, 5)
        runner = DeviceWindowRunner()  # no registry -> auto-registering
        runner.execute(tasks, bufs)
        got = np.stack([np.asarray(b.value) for b in bufs])
        np.testing.assert_array_equal(got, ref)
        assert "axpy" in runner.registry and "mul" in runner.registry


class TestLegacyUniformPath:
    @pytest.mark.parametrize("seed", [0, 2])
    def test_execute_uniform_matches_serial(self, registry, seed):
        _, ref_bufs, ref_tasks = build(seed, 30, 6)
        run_serial(ref_tasks)
        ref = np.stack([np.asarray(b.value) for b in ref_bufs])

        _, dev_bufs, dev_tasks = build(seed, 30, 6)
        runner = DeviceWindowRunner(registry, window_size=16)
        report = runner.execute_uniform(dev_tasks, dev_bufs)
        got = np.stack([np.asarray(b.value) for b in dev_bufs])

        np.testing.assert_allclose(got, ref, rtol=1e-5)
        assert report.exec_stats["dispatches"] == 1

    def test_over_arity_task_raises(self, registry):
        """Seed bug: the legacy tables silently truncated operand lists at
        MAX_ARITY; now they refuse loudly (the arena path has no limit)."""
        pool = BufferPool()
        bufs = [pool.alloc((D,), np.float32, value=jnp.ones(D)) for _ in range(5)]
        ins = tuple(bufs[:MAX_ARITY + 1])
        outs = (bufs[4],)
        r, w = default_segments(ins, outs)
        task = Task(opcode="axpy", fn=lambda *a: sum(a), inputs=ins,
                    outputs=outs, read_segments=r, write_segments=w)
        with pytest.raises(ValueError, match="legacy uniform-slab path"):
            compile_wave_plan([[task]], registry,
                              {b.name: i for i, b in enumerate(bufs)}, len(bufs))

    def test_multi_output_task_raises(self, registry):
        """The legacy tables hold one out-row per slot; multi-output tasks
        must refuse loudly instead of dropping outputs[1:]."""
        pool = BufferPool()
        bufs = [pool.alloc((D,), np.float32, value=jnp.ones(D)) for _ in range(4)]
        ins = (bufs[0], bufs[1])
        outs = (bufs[2], bufs[3])
        r, w = default_segments(ins, outs)
        task = Task(opcode="axpy", fn=lambda x, y: (x + y, x - y), inputs=ins,
                    outputs=outs, read_segments=r, write_segments=w)
        with pytest.raises(ValueError, match="exactly one"):
            compile_wave_plan([[task]], registry,
                              {b.name: i for i, b in enumerate(bufs)}, len(bufs))

    def test_fnless_registration_blocks_branches(self):
        reg = DeviceOpRegistry()
        reg.register("real_kernel")  # fn-less: arena-only opcode
        with pytest.raises(ValueError, match="legacy uniform path"):
            _ = reg.branches


class TestPlanModes:
    def test_plan_respects_dependencies(self, registry):
        _, bufs, tasks = build(3, 24, 5)
        waves = plan_waves(tasks, window_size=16)
        pos = {}
        for wi, wave in enumerate(waves):
            for t in wave:
                pos[t.tid] = wi
        # every task appears exactly once
        flat = [t.tid for w in waves for t in w]
        assert sorted(flat) == sorted(t.tid for t in tasks)
        # dependencies (recomputed all-pairs) must map to strictly earlier waves
        from repro.core import depends_on

        for j, newer in enumerate(tasks):
            for older in tasks[:j]:
                if depends_on(
                    newer.read_segments, newer.write_segments,
                    older.read_segments, older.write_segments,
                ):
                    assert pos[older.tid] < pos[newer.tid]

    def test_plan_frontier_respects_dependencies(self, registry):
        _, bufs, tasks = build(3, 24, 5)
        groups = plan_frontier(tasks, window_size=16)
        pos = {}
        for gi, group in enumerate(groups):
            for t in group:
                pos[t.tid] = gi
        flat = [t.tid for g in groups for t in g]
        assert sorted(flat) == sorted(t.tid for t in tasks)
        from repro.core import depends_on

        for j, newer in enumerate(tasks):
            for older in tasks[:j]:
                if depends_on(
                    newer.read_segments, newer.write_segments,
                    older.read_segments, older.write_segments,
                ):
                    assert pos[older.tid] < pos[newer.tid]

    def test_return_window_exposes_planning_stats(self):
        _, _, tasks = build(1, 20, 5)
        waves, window = plan_waves(tasks, window_size=8, return_window=True)
        assert window.stats.inserted == 20
        assert window.stats.retired == 20
        assert sum(len(w) for w in waves) == 20


class TestLoopPallasCap:
    """Epochs eligible for the Pallas ready queue but over its SMEM/VMEM
    cap run on the lax.while_loop interpreter, and say so."""

    @staticmethod
    def _chains(n_chains, depth):
        from repro.kernels.ops import LOOP_BRANCHES

        rng = np.random.RandomState(3)
        pool = BufferPool()
        states = [pool.alloc((D,), np.float32,
                             value=jnp.asarray(rng.randn(D).astype(np.float32)))
                  for _ in range(n_chains)]
        weight = pool.alloc((D,), np.float32,
                            value=jnp.asarray(rng.randn(D).astype(np.float32)))
        tasks = []
        for s in states:
            for k in range(depth):
                op = "axpy" if k % 2 == 0 else "mul"
                r, w = default_segments((s, weight), (s,))
                tasks.append(Task(opcode=op, fn=LOOP_BRANCHES[op],
                                  inputs=(s, weight), outputs=(s,),
                                  read_segments=r, write_segments=w))
        return states, tasks

    def _reference(self, n_chains, depth):
        states, tasks = self._chains(n_chains, depth)
        run_serial(tasks)
        return np.stack([np.asarray(s.value) for s in states])

    @pytest.mark.parametrize("depth,executor,over", [
        (4, "pallas", False),
        (None, "interpreter", True),   # MAX_TASKS + 2 tasks
    ])
    def test_runner_reports_executor_and_cap(self, depth, executor, over):
        from repro.kernels.ops import register_loop_branches
        from repro.kernels.ready_queue import MAX_TASKS

        depth = depth or MAX_TASKS // 2 + 1
        ref = self._reference(2, depth)
        states, tasks = self._chains(2, depth)
        runner = DeviceWindowRunner(plan_mode="loop", loop_pallas=True)
        register_loop_branches(runner.registry)
        report = runner.run(tasks)
        assert report.loop_executor == executor
        assert report.loop_pallas_over_cap is over
        np.testing.assert_array_equal(
            np.stack([np.asarray(s.value) for s in states]), ref)

    def test_session_counts_over_cap_epochs(self):
        from repro.core import DeviceSession
        from repro.kernels.ops import register_loop_branches
        from repro.kernels.ready_queue import MAX_TASKS

        depth = MAX_TASKS // 2 + 1
        ref = self._reference(2, depth)
        states, tasks = self._chains(2, depth)
        session = DeviceSession(plan_mode="loop", loop_pallas=True)
        register_loop_branches(session.registry)
        session.submit(tasks)
        session.close()
        assert session.session_stats()["loop_pallas_over_cap"] == 1
        np.testing.assert_array_equal(
            np.stack([np.asarray(s.value) for s in states]), ref)
