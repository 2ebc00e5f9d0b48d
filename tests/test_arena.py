"""Shape-class slab arena + arena device path on the REAL workloads.

The acceptance bar of DESIGN §2 A3's generalization: the device-resident
window must run the same sim-engine and dynamic-DNN streams the host
schedulers run — mixed shape classes, variable arity, row-view aliasing,
multi-output tasks — bit-identically to the serial baseline, in ONE
dispatch per stream.
"""

import numpy as np
import pytest
from _prophelper import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import (
    BufferPool,
    DeviceOpRegistry,
    DeviceWindowRunner,
    SlabArena,
    Task,
    TaskStream,
    make_scheduler,
    pad_shape,
    row_capacity,
    run_serial,
)
from repro.core.task import default_segments

PLAN_MODES = ("wave", "frontier")

# A few shape classes that exercise padding, collisions, and rank variety.
SHAPES = [(5,), (7,), (8,), (3, 6), (3, 8), (2, 4, 6)]
DTYPES = [np.float32, np.int32]


# ---------------------------------------------------------------------------
# Arena mechanics
# ---------------------------------------------------------------------------

class TestSlabArena:
    def test_pad_shape(self):
        assert pad_shape((5,), 8) == (8,)
        assert pad_shape((3, 6), 8) == (3, 8)
        assert pad_shape((8,), 8) == (8,)
        assert pad_shape((3, 6), 1) == (3, 6)
        assert pad_shape((), 8) == ()

    def test_shape_collision_shares_class(self):
        """(5,) and (7,) pad to (8,) -> same slab, distinct rows, and the
        per-operand true shape survives the round trip."""
        pool = BufferPool()
        a = pool.alloc((5,), np.float32, value=jnp.arange(5, dtype=jnp.float32))
        b = pool.alloc((7,), np.float32, value=jnp.arange(7, dtype=jnp.float32))
        arena = SlabArena(pad_multiple=8)
        ca, ra = arena.add(a)
        cb, rb = arena.add(b)
        assert ca == cb and ra != rb
        assert arena.n_classes() == 1
        slabs = arena.pack()
        # one row per buffer, physical capacity quantized (row_capacity)
        assert slabs[0].shape == (row_capacity(2), 8)
        assert len(arena.rows(0)) == 2
        arena.unpack(slabs)
        np.testing.assert_array_equal(np.asarray(a.value), np.arange(5, dtype=np.float32))
        np.testing.assert_array_equal(np.asarray(b.value), np.arange(7, dtype=np.float32))

    def test_dtype_splits_class(self):
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8)
        f = pool.alloc((8,), np.float32, value=jnp.zeros(8))
        i = pool.alloc((8,), np.int32, value=jnp.zeros(8, jnp.int32))
        assert arena.add(f)[0] != arena.add(i)[0]

    def test_view_addressing_and_byte_view_rejection(self):
        pool = BufferPool()
        buf = pool.alloc((6, 4), np.float32, value=jnp.zeros((6, 4)))
        arena = SlabArena(pad_multiple=8)
        addr = arena.address(buf.row_view(2, 3))
        assert addr.is_view and addr.row_start == 2 and addr.row_count == 3
        assert addr.class_id == arena.add(buf)[0]
        with pytest.raises(ValueError, match="row views"):
            arena.address(buf.view(0, 16))  # raw byte view: no row semantics

    def test_padding_waste_metric(self):
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8)
        arena.add(pool.alloc((6,), np.float32, value=jnp.zeros(6)))
        waste = arena.padding_waste()
        (entry,) = waste.values()
        assert entry["rows"] == 1
        assert entry["padded_elems_per_row"] == 8
        assert entry["used_elems"] == 6
        assert entry["waste_frac"] == 0.25
        assert arena.total_waste_frac() == pytest.approx(0.25)

    @given(st.lists(st.tuples(st.integers(0, len(SHAPES) - 1),
                              st.integers(0, len(DTYPES) - 1)),
                    min_size=1, max_size=12),
           st.integers(0, 10_000))
    @settings(max_examples=20, deadline=None)
    def test_roundtrip_preserves_values_mixed_classes(self, picks, seed):
        """Property: pack -> execute (copy tasks) -> unpack preserves every
        buffer bit-exactly — untouched buffers through padding/slicing, and
        written buffers bit-identical to the serial baseline."""
        rng = np.random.RandomState(seed)

        def build():
            pool = BufferPool()
            bufs = []
            for si, di in picks:
                shape, dtype = SHAPES[si], DTYPES[di]
                val = (rng.randn(*shape) * 8).astype(dtype)
                bufs.append(pool.from_array(jnp.asarray(val)))
            # copy tasks within a shape/dtype class (same true shape)
            tasks = []
            by_key = {}
            for b in bufs:
                by_key.setdefault((tuple(b.shape), str(np.dtype(b.dtype))), []).append(b)
            for group in by_key.values():
                for src, dst in zip(group, group[1:]):
                    r, w = default_segments((src,), (dst,))
                    tasks.append(Task(opcode="copy", fn=lambda x: x + x.dtype.type(1),
                                      inputs=(src,), outputs=(dst,),
                                      read_segments=r, write_segments=w))
            return bufs, tasks

        state = rng.get_state()
        ref_bufs, ref_tasks = build()
        if ref_tasks:
            run_serial(ref_tasks)
        ref = [np.asarray(b.value) for b in ref_bufs]

        rng.set_state(state)
        dev_bufs, dev_tasks = build()
        if dev_tasks:
            DeviceWindowRunner(window_size=8).execute(dev_tasks, dev_bufs)
        else:  # no tasks: pure pack/unpack round trip
            arena = SlabArena()
            for b in dev_bufs:
                arena.add(b)
            arena.unpack(arena.pack())
        for b, r in zip(dev_bufs, ref):
            np.testing.assert_array_equal(np.asarray(b.value), r)


# ---------------------------------------------------------------------------
# Real workload equivalence (the ISSUE acceptance bar)
# ---------------------------------------------------------------------------

def sim_setup(seed=0, n_envs=4, group_size=2, steps=2):
    from repro.sim import ENVIRONMENTS, PhysicsEngine

    eng = PhysicsEngine(ENVIRONMENTS["cheetah"], n_envs=n_envs,
                        group_size=group_size, seed=seed)
    stream = TaskStream()
    eng.emit_batch(stream, steps)
    return eng, stream.tasks


def dyn_setup(seed=0):
    from repro.dyn import WORKLOADS

    init_fn, build_fn, _ = WORKLOADS["dynamic_routing"]
    rng = np.random.RandomState(seed)
    x = rng.randn(1, 3, 32, 32).astype(np.float32)
    params = init_fn(0)
    stream = TaskStream()
    out = build_fn(params, stream, x)
    return out, stream.tasks


class TestDeviceRunsRealWorkloads:
    @pytest.mark.parametrize("plan_mode", PLAN_MODES)
    def test_sim_stream_matches_serial(self, plan_mode):
        eng_ref, tasks_ref = sim_setup()
        run_serial(tasks_ref)
        ref = eng_ref.state_snapshot()

        eng_dev, tasks_dev = sim_setup()
        from repro.sim import register_device_kernels

        registry = DeviceOpRegistry()
        register_device_kernels(registry)  # strict: the fixed HW opcode set
        runner = DeviceWindowRunner(registry, window_size=32, plan_mode=plan_mode)
        report = runner.run(tasks_dev)

        np.testing.assert_array_equal(eng_dev.state_snapshot(), ref)
        assert report.exec_stats["dispatches"] == 1
        assert report.exec_stats["tasks_run"] == len(tasks_dev)
        assert report.arena_stats["n_classes"] >= 2
        assert report.window_stats["inserted"] == len(tasks_dev)
        # row-view aliasing classes recorded per opcode
        assert "joint_solve" in registry.classes_seen

    @pytest.mark.parametrize("plan_mode", PLAN_MODES)
    def test_dyn_stream_matches_serial(self, plan_mode):
        out_ref, tasks_ref = dyn_setup()
        run_serial(tasks_ref)
        ref = np.asarray(out_ref.value)

        out_dev, tasks_dev = dyn_setup()
        from repro.dyn.blocks import register_device_kernels

        registry = DeviceOpRegistry()
        register_device_kernels(registry)
        report = DeviceWindowRunner(registry, window_size=32,
                                    plan_mode=plan_mode).run(tasks_dev)

        np.testing.assert_array_equal(np.asarray(out_dev.value), ref)
        assert report.exec_stats["dispatches"] == 1
        assert report.arena_stats["n_classes"] >= 2
        assert 0.0 <= report.arena_stats["total_waste_frac"] < 1.0

    def test_make_scheduler_device_contract(self):
        """`make_scheduler("device")` returns a runner conforming to the
        SchedulerReport contract the host schedulers satisfy."""
        eng_ref, tasks_ref = sim_setup(steps=1)
        run_serial(tasks_ref)
        ref = eng_ref.state_snapshot()

        eng_dev, tasks_dev = sim_setup(steps=1)
        run = make_scheduler("device", window_size=32, plan_mode="frontier")
        report = run(tasks_dev)

        np.testing.assert_array_equal(eng_dev.state_snapshot(), ref)
        assert report.exec_stats["dispatches"] == 1
        assert report.window_stats["inserted"] == len(tasks_dev)
        assert 0.0 < report.occupancy_proxy() <= 1.0
        assert report.wall_seconds > 0
        assert report.plan_mode == "frontier"

    def test_make_scheduler_rejects_bad_plan_mode(self):
        with pytest.raises(ValueError, match="plan_mode"):
            make_scheduler("device", plan_mode="bogus")


class TestMultiOutputAndArity:
    def test_multi_output_task(self):
        """The arena path scatters every output of a multi-output task."""
        def split(x, y):
            return x + y, x - y

        def build():
            pool = BufferPool()
            a = pool.alloc((6,), np.float32, value=jnp.arange(6, dtype=jnp.float32))
            b = pool.alloc((6,), np.float32, value=jnp.ones(6))
            s = pool.alloc((6,), np.float32)
            d = pool.alloc((6,), np.float32)
            r, w = default_segments((a, b), (s, d))
            t = Task(opcode="split", fn=split, inputs=(a, b), outputs=(s, d),
                     read_segments=r, write_segments=w)
            return (s, d), [t]

        outs_ref, tasks_ref = build()
        run_serial(tasks_ref)
        outs_dev, tasks_dev = build()
        report = DeviceWindowRunner().run(tasks_dev)
        for dev, ref in zip(outs_dev, outs_ref):
            np.testing.assert_array_equal(np.asarray(dev.value), np.asarray(ref.value))
        assert report.exec_stats["dispatches"] == 1

    def test_signature_equal_view_and_buffer_do_not_group(self):
        """Regression: a full (2,4) buffer and a 2-row view of an (8,4)
        buffer are Task.signature-equal (same value shape) but need
        different gather code — lowering must split them into separate
        steps, not take the first task's addressing for both."""
        def bump(x):
            return x + 1.0

        def build():
            pool = BufferPool()
            small = pool.alloc((2, 4), np.float32,
                               value=jnp.full((2, 4), 10.0))
            big = pool.alloc((8, 4), np.float32,
                             value=jnp.full((8, 4), 100.0))
            outs = [pool.alloc((2, 4), np.float32) for _ in range(2)]
            tasks = []
            for src, dst in ((small, outs[0]), (big.row_view(2, 2), outs[1])):
                r, w = default_segments((src,), (dst,))
                tasks.append(Task(opcode="bump", fn=bump, inputs=(src,),
                                  outputs=(dst,), read_segments=r,
                                  write_segments=w))
            return outs, tasks

        outs_ref, tasks_ref = build()
        run_serial(tasks_ref)
        outs_dev, tasks_dev = build()
        report = DeviceWindowRunner().run(tasks_dev)
        assert report.exec_stats["dispatches"] == 1
        for dev, ref in zip(outs_dev, outs_ref):
            np.testing.assert_array_equal(np.asarray(dev.value),
                                          np.asarray(ref.value))

    def test_variable_arity_beyond_legacy_limit(self):
        """Arity > MAX_ARITY lowers fine through the arena (the sim
        integrate kernel relies on this)."""
        def sum5(a, b, c, d, e):
            return a + b + c + d + e

        def build():
            pool = BufferPool()
            ins = tuple(pool.alloc((4,), np.float32,
                                   value=jnp.full(4, float(i + 1)))
                        for i in range(5))
            out = pool.alloc((4,), np.float32)
            r, w = default_segments(ins, (out,))
            return out, [Task(opcode="sum5", fn=sum5, inputs=ins, outputs=(out,),
                              read_segments=r, write_segments=w)]

        out_ref, t_ref = build()
        run_serial(t_ref)
        out_dev, t_dev = build()
        DeviceWindowRunner().run(t_dev)
        np.testing.assert_array_equal(np.asarray(out_dev.value),
                                      np.asarray(out_ref.value))


# ---------------------------------------------------------------------------
# Row lifecycle: free / recycle / compact (DESIGN §2 A3 gap (2))
# ---------------------------------------------------------------------------

class TestRowLifecycle:
    def _mk(self, pool, n, shape=(6,), dtype=np.float32, base=0.0):
        return [pool.alloc(shape, dtype,
                           value=jnp.full(shape, base + i, dtype=dtype))
                for i in range(n)]

    def test_free_then_add_recycles_the_row(self):
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8)
        a, b = self._mk(pool, 2)
        addr_a = arena.add(a)
        arena.add(b)
        assert arena.free(a) and a not in arena
        c = pool.alloc((6,), np.float32, value=jnp.zeros(6))
        assert arena.add(c) == addr_a  # reuse, not growth
        assert arena.recycled_rows == 1 and arena.freed_rows == 1
        assert len(arena.rows(0)) == 2  # slab never grew

    def test_free_unknown_buffer_is_noop(self):
        pool = BufferPool()
        arena = SlabArena()
        assert arena.free(pool.alloc((4,), np.float32, value=jnp.zeros(4))) is False
        assert arena.freed_rows == 0

    def test_recycled_packed_row_refreshed_on_pack_incremental(self):
        """A recycled row below the watermark holds the dead occupant's
        device bits; the next incremental pack must rewrite it from the new
        buffer's host value."""
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8)
        a, b = self._mk(pool, 2)
        arena.add(a), arena.add(b)
        slabs = arena.pack()
        arena.free(a)
        c = pool.alloc((6,), np.float32, value=jnp.full(6, 42.0))
        cid, row = arena.add(c)
        slabs = arena.pack_incremental(slabs)
        assert slabs[cid].shape[0] == row_capacity(2)
        assert len(arena.rows(cid)) == 2
        np.testing.assert_array_equal(np.asarray(slabs[cid][row][:6]),
                                      np.full(6, 42.0, np.float32))

    def test_full_pack_zeroes_dead_rows_and_unpack_skips_them(self):
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8)
        bufs = self._mk(pool, 3)
        for b in bufs:
            arena.add(b)
        arena.free(bufs[1])
        slabs = arena.pack()
        np.testing.assert_array_equal(np.asarray(slabs[0][1]), np.zeros(8))
        arena.unpack(slabs)  # must not touch the dead row's old buffer
        np.testing.assert_array_equal(np.asarray(bufs[1].value),
                                      np.full(6, 1.0, np.float32))

    def test_unpack_only_is_addressed_not_scanned(self):
        """unpack(only=...) resolves through the address map: exactly
        |only| rows written, released buffers silently skipped."""
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8)
        bufs = self._mk(pool, 4)
        for b in bufs:
            arena.add(b)
        slabs = arena.pack()
        arena.free(bufs[3])
        arena.unpack(slabs, only=[bufs[2], bufs[3]])
        assert arena.unpack_rows_written == 1  # bufs[3] released -> skipped

    def test_needs_compaction_threshold(self):
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8, compact_waste=0.5,
                          compact_min_rows=4)
        bufs = self._mk(pool, 4)
        for b in bufs:
            arena.add(b)
        arena.free(bufs[0])
        assert arena.needs_compaction() == []  # 1/4 < 0.5
        arena.free(bufs[1])
        assert arena.needs_compaction() == [0]  # 2/4 >= 0.5
        small = SlabArena(compact_min_rows=8)
        b = pool.alloc((6,), np.float32, value=jnp.zeros(6))
        small.add(b)
        small.free(b)
        assert small.needs_compaction() == []  # under min_rows floor

    def test_compact_gathers_device_values_and_remaps(self):
        """Compaction drops dead rows from the materialized slab WITHOUT a
        host round-trip, remaps surviving addresses densely in old order,
        and bumps the class generation."""
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8, compact_min_rows=2)
        bufs = self._mk(pool, 6)
        for b in bufs:
            arena.add(b)
        slabs = arena.pack()
        # poison host values: post-compaction unpack must read DEVICE rows
        for b in bufs:
            b.value = jnp.full(6, -99.0)
        for i in (0, 2, 4):
            arena.free(bufs[i])
        assert arena.needs_compaction() == [0]
        slabs, moved = arena.compact(slabs)
        assert moved == {0: {1: 0, 3: 1, 5: 2}}
        assert arena.generation == 1 and arena.class_generation(0) == 1
        assert arena.compactions == 1
        assert slabs[0].shape[0] == row_capacity(3) and len(arena.rows(0)) == 3
        assert arena.free_rows() == 0
        for b in (bufs[1], bufs[3], bufs[5]):
            cid, row = arena.add(b)  # idempotent lookup of the new address
            np.testing.assert_array_equal(
                np.asarray(slabs[cid][row][:6]),
                np.full(6, float(bufs.index(b)), np.float32))

    def test_compact_keeps_unpacked_tail_on_host(self):
        """Rows beyond the watermark were never materialized: compaction
        must not invent device values for them — the next incremental pack
        appends them from host as usual."""
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8, compact_min_rows=2)
        a, b = self._mk(pool, 2)
        arena.add(a), arena.add(b)
        slabs = arena.pack()  # watermark = 2
        arena.free(a)
        tail = pool.alloc((6,), np.float32, value=jnp.full(6, 7.0))
        # recycles a's row -> no unpacked tail yet; free b to force waste
        arena.add(tail)
        arena.free(b)
        c = self._mk(pool, 1, base=30.0)[0]
        arena.add(c)
        d = self._mk(pool, 1, base=40.0)[0]
        arena.add(d)  # grows: row 2, beyond current watermark
        slabs = arena.pack_incremental(slabs)  # watermark = 3
        arena.free(c)
        arena.free(tail)
        slabs, moved = arena.compact(slabs)
        slabs = arena.pack_incremental(slabs)
        arena.unpack(slabs)
        np.testing.assert_array_equal(np.asarray(d.value), np.full(6, 40.0))

    @given(st.lists(st.integers(0, 2), min_size=1, max_size=60),
           st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_lifecycle_never_aliases_live_rows(self, ops, seed):
        """Property: under any add/free/compact interleaving, live buffers
        occupy distinct rows, free-list rows are exactly the dead ones, and
        packed slabs always reproduce every live host value."""
        rng = np.random.RandomState(seed)
        pool = BufferPool()
        arena = SlabArena(pad_multiple=8, compact_min_rows=2)
        live = []
        expected = {}
        slabs = None
        counter = [0]
        for op in ops:
            if op == 0 or not live:  # add
                counter[0] += 1
                b = pool.alloc((5,), np.float32,
                               value=jnp.full(5, float(counter[0])))
                arena.add(b)
                live.append(b)
                expected[id(b)] = float(counter[0])
            elif op == 1:  # free a random live buffer
                b = live.pop(rng.randint(len(live)))
                assert arena.free(b)
            else:  # compact (threshold-driven)
                slabs, _ = arena.compact(slabs)
            if rng.rand() < 0.4:
                slabs = arena.pack_incremental(slabs)
        # no aliasing: every live buffer has a unique address
        addrs = [arena.add(b) for b in live]
        assert len(set(addrs)) == len(addrs)
        # free-list accounting
        assert arena.live_rows() == len(live)
        assert arena.live_rows() + arena.free_rows() == \
            sum(len(arena.rows(c)) for c in range(arena.n_classes()))
        # every live value survives the round trip
        slabs = arena.pack_incremental(slabs)
        arena.unpack(slabs)
        for b in live:
            np.testing.assert_array_equal(
                np.asarray(b.value), np.full(5, expected[id(b)], np.float32))


# case -> (buffer shapes, dtype, indices passed as ``only`` (None: every
# live row), indices released before the read-back, gathers expected).
# Slab capacity is row_capacity(rows): a class whose touched rows fill at
# least half of it is read whole, any other through one gather.
UNPACK_CASES = {
    "f32-padded-dense": ([(5,)] * 3 + [(7,)] * 2 + [(3, 6)] * 4,
                         np.float32, None, (), 0),
    "f32-unpadded-sparse": ([(8,)] * 2 + [(3, 8)], np.float32, None, (), 2),
    "bf16-padded-dense": ([(2, 6)] * 4 + [(2, 5)], jnp.bfloat16, None, (), 0),
    "i32-dense-and-sparse": ([(3, 6)] * 5 + [(16,)], np.int32, None, (), 1),
    "f32-scalars": ([()] * 4, np.float32, None, (), 0),
    "only-sparse": ([(5,)] * 12, np.float32, [0, 7, 11], (), 1),
    "only-dense": ([(5,)] * 12, np.float32, list(range(8)), (), 0),
    "dead-row": ([(5,)] * 6, np.float32, None, (2,), 0),
    "released-in-only": ([(5,)] * 6 + [(3, 6)], np.float32, [1, 2, 3, 6],
                         (2,), 2),
    "only-released": ([(5,)] * 3, np.float32, [1], (1,), 0),
}


@pytest.mark.parametrize("case", sorted(UNPACK_CASES))
def test_batched_unpack_matches_per_row_slicing(case, monkeypatch):
    """``unpack`` reads each touched class back in one transfer and cuts
    rows and padding on the host: every value equals the per-row device
    slice bit for bit, as its own NumPy array; released and untouched
    buffers keep their values; and the host values go back to the device
    unchanged through ``update_rows`` and ``pack_incremental``."""
    import repro.core.arena as arena_mod

    shapes, dtype, only, freed, gathers = UNPACK_CASES[case]
    rng = np.random.RandomState(0)
    pool, arena = BufferPool(), SlabArena(pad_multiple=8)
    bufs = [pool.alloc(s, dtype, value=jnp.asarray(
                rng.randn(*s) * 8, np.float32).astype(dtype))
            for s in shapes]
    for b in bufs:
        arena.add(b)
    # Device rows differ from the host values, padding included: the
    # read-back must come from the slabs.
    slabs = [s + jnp.ones_like(s) for s in arena.pack()]
    for i in freed:
        arena.free(bufs[i])
    picked = range(len(bufs)) if only is None else only
    live = [i for i in picked if i not in freed]
    want = {}
    for i in live:
        cid, row = arena.addr_of(bufs[i])
        cut = tuple(slice(0, s) for s in bufs[i].shape)
        want[i] = np.asarray(slabs[cid][row][cut]).astype(np.float32)
    before = [b.value for b in bufs]
    taken = []
    take = arena_mod._take_rows

    def spy(slab, idx):
        taken.append(len(idx))
        return take(slab, idx)

    monkeypatch.setattr(arena_mod, "_take_rows", spy)
    arena.unpack(slabs, only=None if only is None else [bufs[i] for i in only])

    for i, b in enumerate(bufs):
        if i not in want:
            assert b.value is before[i]
            continue
        assert isinstance(b.value, np.ndarray) and b.value.flags.owndata
        assert b.value.dtype == np.dtype(dtype)
        assert b.value.shape == tuple(b.shape)
        np.testing.assert_array_equal(b.value.astype(np.float32), want[i])
    assert arena.unpack_rows_written == len(live)
    assert arena.unpack_transfers == len(
        {arena.addr_of(bufs[i])[0] for i in live})
    assert len(taken) == gathers
    assert all(n == row_capacity(n) for n in taken)

    mine = [bufs[i] for i in live]
    if not mine:
        return
    again = SlabArena(pad_multiple=8)
    again.add(mine[0])
    fresh = again.pack()
    for b in mine[1:]:
        again.add(b)
    again.unpack(again.pack_incremental(fresh))
    slabs = arena.update_rows(slabs, mine)
    for b in mine:
        b.value = None
    arena.unpack(slabs, only=mine)
    for i in live:
        np.testing.assert_array_equal(bufs[i].value.astype(np.float32),
                                      want[i])


class TestCrossDevicePinnedSlabs:
    """A mesh shard pins its session's slabs to its own device, but the
    buffers fed to it may hold arrays committed to ANOTHER device — a
    shared buffer last written by a different shard's dispatch. Every
    in-place slab update must re-commit the incoming rows to the slab's
    device first, or jax raises its incompatible-devices error (this
    crashed mesh serving of mixed-priority hazard streams under
    ``--xla_force_host_platform_device_count=8``)."""

    pytestmark = pytest.mark.skipif(
        jax.device_count() < 2, reason="needs >= 2 devices")

    def _pinned(self, pool, arena):
        a = pool.alloc((6,), np.float32, value=jnp.zeros(6))
        arena.add(a)
        return a, [jax.device_put(s, jax.devices()[1]) for s in arena.pack()]

    def _committed(self, fill):
        return jax.device_put(jnp.full(6, fill, jnp.float32),
                              jax.devices()[0])

    def test_pack_incremental_appends_foreign_rows(self):
        pool, arena = BufferPool(), SlabArena(pad_multiple=8)
        _, slabs = self._pinned(pool, arena)
        b = pool.alloc((6,), np.float32, value=self._committed(7.0))
        cid, row = arena.add(b)
        slabs = arena.pack_incremental(slabs)
        np.testing.assert_array_equal(np.asarray(slabs[cid][row][:6]),
                                      np.full(6, 7.0, np.float32))

    def test_pack_incremental_refreshes_recycled_foreign_row(self):
        pool, arena = BufferPool(), SlabArena(pad_multiple=8)
        a, slabs = self._pinned(pool, arena)
        arena.free(a)
        c = pool.alloc((6,), np.float32, value=self._committed(9.0))
        cid, row = arena.add(c)  # recycled below the watermark
        slabs = arena.pack_incremental(slabs)
        np.testing.assert_array_equal(np.asarray(slabs[cid][row][:6]),
                                      np.full(6, 9.0, np.float32))

    def test_update_rows_with_foreign_value(self):
        pool, arena = BufferPool(), SlabArena(pad_multiple=8)
        a, slabs = self._pinned(pool, arena)
        a.value = self._committed(3.0)
        addr = arena.address(a)
        slabs = arena.update_rows(slabs, [a])
        np.testing.assert_array_equal(
            np.asarray(slabs[addr.class_id][addr.row][:6]),
            np.full(6, 3.0, np.float32))
