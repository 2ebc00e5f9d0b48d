"""Per-kernel validation: Pallas (interpret=True on CPU) vs ref.py oracles,
swept over shapes and dtypes, plus hypothesis property tests."""

import numpy as np
import pytest
from _prophelper import given, settings, st

import jax
import jax.numpy as jnp

from repro.kernels import (
    apply_wave,
    flash_attention,
    grouped_matmul,
    lru_scan,
    wave_elementwise,
)
from repro.kernels import ref

RNG = np.random.RandomState(0)


def randn(*shape, dtype=np.float32):
    return jnp.asarray(RNG.randn(*shape).astype(dtype))


TOL = {np.float32: dict(rtol=2e-5, atol=2e-5), np.float16: dict(rtol=2e-2, atol=2e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,hkv,sq,sk,d", [
        (1, 2, 2, 32, 32, 16),    # MHA
        (2, 4, 2, 48, 48, 32),    # GQA 2:1, non-pow2 seq (padding path)
        (1, 8, 1, 16, 64, 8),     # MQA, cross Sq != Sk
    ])
    @pytest.mark.parametrize("dtype", [np.float32, np.float16])
    def test_shapes_dtypes_causal(self, b, h, hkv, sq, sk, d, dtype):
        q, k, v = randn(b, h, sq, d, dtype=dtype), randn(b, hkv, sk, d, dtype=dtype), randn(b, hkv, sk, d, dtype=dtype)
        off = sk - sq
        out = flash_attention(q, k, v, q_offset=off, block_q=16, block_k=16)
        expect = ref.attention_ref(q, k, v, q_offset=off)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), **TOL[dtype])

    @pytest.mark.parametrize("window", [8, 17])
    def test_local_window(self, window):
        q, k, v = randn(1, 2, 40, 16), randn(1, 2, 40, 16), randn(1, 2, 40, 16)
        out = flash_attention(q, k, v, window=window, block_q=8, block_k=8)
        expect = ref.attention_ref(q, k, v, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        q, k, v = randn(1, 2, 32, 16), randn(1, 2, 32, 16), randn(1, 2, 32, 16)
        out = flash_attention(q, k, v, softcap=10.0, block_q=16, block_k=16)
        expect = ref.attention_ref(q, k, v, softcap=10.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)

    def test_decode_single_query(self):
        q, k, v = randn(2, 4, 1, 16), randn(2, 2, 128, 16), randn(2, 2, 128, 16)
        out = flash_attention(q, k, v, q_offset=127, block_q=1, block_k=32)
        expect = ref.attention_ref(q, k, v, q_offset=127)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)

    def test_noncausal(self):
        q, k, v = randn(1, 2, 24, 16), randn(1, 2, 24, 16), randn(1, 2, 24, 16)
        out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8)
        expect = ref.attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)

    @given(st.integers(1, 3), st.integers(0, 2), st.integers(3, 6), st.integers(2, 5))
    @settings(max_examples=10, deadline=None)
    def test_property_gqa_any_shape(self, b, hkv_log, sq_log, d_log):
        hkv = 2 ** hkv_log
        h = hkv * 2
        sq = 2 ** sq_log
        d = 2 ** d_log
        q, k, v = randn(b, h, sq, d), randn(b, hkv, sq, d), randn(b, hkv, sq, d)
        out = flash_attention(q, k, v, block_q=8, block_k=8)
        expect = ref.attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=3e-5, atol=3e-5)


class TestGroupedMatmul:
    @pytest.mark.parametrize("g,k,n,bm,tiles", [
        (2, 16, 16, 8, (0, 1)),
        (4, 32, 48, 8, (0, 0, 1, 2, 2, 3)),
        (8, 64, 24, 16, (0, 2, 2, 4, 7)),   # n not multiple of block_n
    ])
    @pytest.mark.parametrize("dtype", [np.float32, np.float16])
    def test_shapes_dtypes(self, g, k, n, bm, tiles, dtype):
        tiles = jnp.asarray(tiles, jnp.int32)
        m = len(tiles) * bm
        x = randn(m, k, dtype=dtype)
        w = randn(g, k, n, dtype=dtype)
        out = grouped_matmul(x, w, tiles, block_m=bm, block_n=16)
        expect = ref.grouped_matmul_ref(x, w, tiles, block_m=bm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), **TOL[dtype])

    @given(st.integers(1, 6), st.integers(1, 4))
    @settings(max_examples=10, deadline=None)
    def test_property_random_tiling(self, n_tiles, g):
        tiles = jnp.asarray(np.random.RandomState(n_tiles).randint(0, g, n_tiles), jnp.int32)
        bm, k, n = 8, 16, 16
        x = randn(n_tiles * bm, k)
        w = randn(g, k, n)
        out = grouped_matmul(x, w, tiles, block_m=bm, block_n=16)
        expect = ref.grouped_matmul_ref(x, w, tiles, block_m=bm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


class TestLruScan:
    @pytest.mark.parametrize("b,s,d,chunk", [
        (1, 16, 8, 4),
        (2, 33, 16, 8),   # padding path (s % chunk != 0)
        (3, 64, 4, 64),   # single chunk
    ])
    def test_shapes(self, b, s, d, chunk):
        a = jnp.asarray(RNG.uniform(0.5, 0.99, (b, s, d)).astype(np.float32))
        x = randn(b, s, d)
        h0 = randn(b, d)
        out = lru_scan(a, x, h0, chunk=chunk)
        expect = ref.lru_scan_ref(a, x, h0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)

    def test_identity_decay_keeps_state(self):
        b, s, d = 1, 8, 4
        a = jnp.ones((b, s, d))
        x = jnp.zeros((b, s, d))
        h0 = randn(b, d)
        out = lru_scan(a, x, h0, chunk=4)
        np.testing.assert_allclose(np.asarray(out[:, -1]), np.asarray(h0), rtol=1e-6)

    @given(st.integers(1, 3), st.integers(1, 40), st.integers(1, 8))
    @settings(max_examples=10, deadline=None)
    def test_property_matches_scan(self, b, s, d):
        rng = np.random.RandomState(s * 7 + d)
        a = jnp.asarray(rng.uniform(0.0, 1.0, (b, s, d)).astype(np.float32))
        x = jnp.asarray(rng.randn(b, s, d).astype(np.float32))
        h0 = jnp.asarray(rng.randn(b, d).astype(np.float32))
        out = lru_scan(a, x, h0, chunk=8)
        expect = ref.lru_scan_ref(a, x, h0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=3e-5, atol=3e-5)


_BRANCHES = (
    lambda x, y: x + y,
    lambda x, y: x * y,
    lambda x, y: jnp.maximum(x, y),
)


class TestWaveElementwise:
    def test_single_wave_matches_ref(self):
        slab = randn(6, 16)
        desc = jnp.asarray([[0, 0, 1, 4], [1, 2, 3, 5]], jnp.int32)
        rows = wave_elementwise(slab, desc, branches=_BRANCHES)
        expect = ref.wave_elementwise_ref(
            slab, np.asarray(desc[:, 0]), np.asarray(desc[:, 1:3]),
            np.asarray(desc[:, 3]), _BRANCHES,
        )
        got = apply_wave(slab, desc, rows)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=1e-6)

    @given(st.integers(0, 1000))
    @settings(max_examples=15, deadline=None)
    def test_property_random_waves(self, seed):
        rng = np.random.RandomState(seed)
        r, d, s = 8, 8, 5
        slab = jnp.asarray(rng.randn(r, d).astype(np.float32))
        ops = rng.randint(0, len(_BRANCHES), s)
        ins = rng.randint(0, r, (s, 2))
        outs = rng.choice(r, s, replace=False)  # unique out rows (window invariant)
        desc = jnp.asarray(np.concatenate([ops[:, None], ins, outs[:, None]], axis=1), jnp.int32)
        rows = wave_elementwise(slab, desc, branches=_BRANCHES)
        got = apply_wave(slab, desc, rows)
        expect = ref.wave_elementwise_ref(slab, ops, ins, outs, _BRANCHES)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect), rtol=1e-6)


class TestReadyQueue:
    """The Pallas ready-queue kernel (interpret mode here) against the
    program-order baseline: random forward DAGs over a row slab, every
    task one LOOP_BRANCHES kernel writing one row."""

    @staticmethod
    def _dag(seed, n, rows, width):
        from repro.kernels.ops import LOOP_BRANCHES

        rng = np.random.RandomState(seed)
        fns = list(LOOP_BRANCHES.values())
        slab = rng.randn(rows, width).astype(np.float32)
        tasks = [(int(rng.randint(len(fns))), int(rng.randint(rows)),
                  int(rng.randint(rows)), int(rng.randint(rows)))
                 for _ in range(n)]
        # forward edges as a scoreboard finds them: RAW and WAW from each
        # row's last writer, WAR from the row's readers since that write
        deps = [set() for _ in range(n)]
        writer, readers = {}, {}
        for j, (_, a, b, o) in enumerate(tasks):
            ups = {writer[r] for r in (a, b, o) if r in writer}
            ups |= readers.get(o, set())
            for i in ups - {j}:
                deps[i].add(j)
            for r in (a, b):
                readers.setdefault(r, set()).add(j)
            writer[o], readers[o] = j, set()
        indeg = np.zeros(n + 1, np.int32)
        for ds in deps:
            for j in ds:
                indeg[j] += 1
        m = max(1, max(len(ds) for ds in deps))
        dep_tbl = np.full((n, m), n, np.int32)
        for i, ds in enumerate(deps):
            dep_tbl[i, :len(ds)] = sorted(ds)
        ready = np.flatnonzero(indeg[:n] == 0)
        ring0 = np.full(n + 1, n, np.int32)
        ring0[:len(ready)] = ready
        task_tbl = np.asarray([(f, a, b, a, o) for f, a, b, o in tasks],
                              np.int32)
        ref = jnp.asarray(slab)
        for f, a, b, o in tasks:
            ref = ref.at[o].set(jax.jit(fns[f])(ref[a], ref[b]))
        branches = tuple((lambda x, y, z, _f=f: _f(x, y)) for f in fns)
        args = (jnp.asarray(slab), jnp.asarray(task_tbl),
                jnp.asarray(dep_tbl), jnp.asarray(ring0),
                jnp.asarray(indeg), jnp.asarray([len(ready)], np.int32))
        return args, branches, np.asarray(ref)

    @pytest.mark.parametrize("seed,n,rows,width", [
        (0, 12, 6, 16),     # narrow rows: lanes pad 16 -> 128
        (1, 40, 10, 130),   # width just past one vreg: pads to 256
        (2, 64, 4, 128),    # dense hazards over few rows
    ])
    def test_matches_program_order(self, seed, n, rows, width):
        from repro.kernels.ready_queue import ready_queue_call

        args, branches, ref = self._dag(seed, n, rows, width)
        slab, done = ready_queue_call(*args, branches=branches,
                                      interpret=True)
        assert np.asarray(done).tolist() == [1] * n
        np.testing.assert_array_equal(np.asarray(slab), ref)

    def test_over_cap_is_refused(self):
        from repro.kernels.ready_queue import (MAX_DEPS, MAX_SLAB_VMEM_BYTES,
                                               MAX_TASKS, over_cap,
                                               ready_queue_call,
                                               slab_vmem_bytes)

        assert over_cap(MAX_TASKS, MAX_DEPS, 8, 128) is None
        assert "MAX_TASKS" in over_cap(MAX_TASKS + 1, 1, 8, 128)
        assert "MAX_DEPS" in over_cap(8, MAX_DEPS + 1, 8, 128)
        rows = MAX_SLAB_VMEM_BYTES // slab_vmem_bytes(1, 128) + 1
        assert "VMEM" in over_cap(8, 1, rows, 128)
        args, branches, _ = self._dag(0, 12, 6, 16)
        big = (jnp.zeros((rows, 128), jnp.float32),) + args[1:]
        with pytest.raises(ValueError, match="cap"):
            ready_queue_call(*big, branches=branches, interpret=True)
