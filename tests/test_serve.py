"""Serving runtime: the live SessionServer and the batch-drain baseline
must produce identical tokens, leak no prompt buffers, observe
co-scheduling (prefill alongside in-flight decode), apply multi-tenant
fairness, and exert backpressure through the bounded admission FIFO."""

import dataclasses
import re

import numpy as np
import pytest

import jax

from repro.configs import ARCHS
from repro.models import init_params
from repro.runtime import (
    AdmissionQueueFull,
    ContinuousBatchingServer,
    SessionServer,
)


@pytest.fixture(scope="module")
def tiny_cfg():
    cfg = ARCHS["h2o-danube-3-4b"].reduced()
    return dataclasses.replace(cfg, n_layers=1, d_model=32, d_ff=64, vocab=64,
                               n_heads=2, n_kv_heads=1, head_dim=16)


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return init_params(tiny_cfg, jax.random.PRNGKey(0), tp_size=1)


def _prompts(tiny_cfg, n, seed=0, length=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, tiny_cfg.vocab, length) for _ in range(n)]


def _no_prompt_buffers(pool):
    return [b.name for b in pool.buffers() if b.name.endswith("_prompt")] == []


class TestSessionServer:
    @pytest.mark.parametrize("scheduler", ["frontier", "wave"])
    def test_requests_finish_with_correct_token_counts(self, tiny_cfg, tiny_params,
                                                       scheduler):
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32,
                               scheduler=scheduler)
        reqs = [server.submit(p, max_new=3) for p in _prompts(tiny_cfg, 4)]
        done = server.run_until_drained()
        server.close()
        assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
        for r in done:
            assert len(r.generated) == 3
            assert r.t_finish >= r.t_admit >= r.t_arrival > 0

    def test_tokens_identical_to_batch_server(self, tiny_cfg, tiny_params):
        """Live-window scheduling only reorders provably independent work:
        every request's token sequence must match the per-step drain's."""
        prompts = _prompts(tiny_cfg, 5, seed=1)
        batch = ContinuousBatchingServer(tiny_cfg, tiny_params, max_slots=2,
                                         max_len=32)
        for p in prompts:
            batch.submit(p, max_new=3)
        ref = {tuple(r.prompt): r.generated for r in batch.run_until_drained()}

        live = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32,
                             scheduler="frontier")
        for p in prompts:
            live.submit(p, max_new=3)
        got = {tuple(r.prompt): r.generated for r in live.run_until_drained()}
        live.close()
        assert got == ref

    def test_no_prompt_buffer_leak(self, tiny_cfg, tiny_params):
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32)
        for p in _prompts(tiny_cfg, 4):
            server.submit(p, max_new=2)
        server.run_until_drained()
        server.close()
        assert _no_prompt_buffers(server.pool)

    @pytest.mark.parametrize("n_shards", [1, 2])
    def test_mesh_tokens_identical_to_frontier(self, tiny_cfg, tiny_params,
                                               n_shards):
        """Serving through the mesh-sharded window (DESIGN §12): token
        sequences must match the frontier session's exactly — decode-chain
        retirement callbacks must observe each intermediate slot value
        even when a whole chain drains inside one sub-epoch — and the
        close stats must carry the per-shard slot-occupancy samples."""
        prompts = _prompts(tiny_cfg, 4, seed=2)
        ref_server = SessionServer(tiny_cfg, tiny_params, max_slots=2,
                                   max_len=32, scheduler="frontier")
        for p in prompts:
            ref_server.submit(p, max_new=3)
        ref = {tuple(r.prompt): r.generated
               for r in ref_server.run_until_drained()}
        ref_server.close()

        mesh = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32,
                             scheduler="mesh", n_shards=n_shards)
        for p in prompts:
            mesh.submit(p, max_new=3)
        got = {tuple(r.prompt): r.generated
               for r in mesh.run_until_drained()}
        mesh.close()
        entry = mesh.report_log[-1]
        assert got == ref
        assert _no_prompt_buffers(mesh.pool)
        assert entry["shard_slots_mean"], entry
        assert all(v >= 0 for v in entry["shard_slots_mean"].values())

    def test_coscheduling_prefill_with_inflight_decode(self, tiny_cfg, tiny_params):
        """A request arriving mid-decode shares a wave with the in-flight
        decode (wave) — admission into the LIVE window, not a fresh drain."""
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32,
                               scheduler="wave")
        # task_kinds drops entries at retirement (bounded bookkeeping), so
        # record each retired task's kind through the session listener
        kinds = {}
        server.session.add_retire_listener(
            lambda t: kinds.__setitem__(t.tid, t.opcode))
        prompts = _prompts(tiny_cfg, 2, seed=2)
        server.submit(prompts[0], max_new=4)
        for _ in range(3):
            server.pump()  # request 0 prefilled and decoding
        server.submit(prompts[1], max_new=4)  # arrives mid-decode
        server.run_until_drained()
        report = server.close()
        mixed = [w for w in report.waves
                 if len({kinds[t] for t in w}) > 1]
        assert mixed, "no wave co-scheduled a prefill with the in-flight decode"
        assert not server.task_kinds, "task_kinds must drain with retirements"

    def test_frontier_overlaps_groups(self, tiny_cfg, tiny_params):
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32,
                               scheduler="frontier")
        for p in _prompts(tiny_cfg, 4, seed=3):
            server.submit(p, max_new=3)
        server.run_until_drained()
        report = server.close()
        assert report.max_inflight_groups() > 1

    def test_tenant_fairness_oldest_first_tiebreak(self, tiny_cfg, tiny_params):
        """Tenant B arriving behind A's backlog is admitted as soon as a
        slot frees (fewest-active-slots rule), ahead of older A requests."""
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32)
        a = [server.submit(p, max_new=2, tenant="A")
             for p in _prompts(tiny_cfg, 4, seed=4)]
        b = server.submit(_prompts(tiny_cfg, 1, seed=5)[0], max_new=2, tenant="B")
        server.run_until_drained()
        server.close()
        assert b.t_admit < a[2].t_admit  # B jumped A's backlog...
        assert a[2].t_admit < a[3].t_admit  # ...but A stays oldest-first

    def test_close_drains_inflight_chains(self, tiny_cfg, tiny_params):
        """Requests still in flight at close() retire during the closing
        flush; one more pump() hands them to the caller."""
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32)
        req = server.submit(_prompts(tiny_cfg, 1, seed=9)[0], max_new=2)
        server.pump()  # admitted; chain in flight, nothing harvested yet
        server.close()
        done = server.pump()
        assert [r.rid for r in done] == [req.rid]
        assert len(req.generated) == 2

    def test_backpressure_bounded_fifo(self, tiny_cfg, tiny_params):
        server = SessionServer(tiny_cfg, tiny_params, max_slots=1, max_len=32,
                               max_queue=2)
        prompts = _prompts(tiny_cfg, 3, seed=6)
        r0 = server.submit(prompts[0])
        r1 = server.submit(prompts[1])
        assert (r0.queue_depth, r1.queue_depth) == (1, 2)
        with pytest.raises(AdmissionQueueFull):
            server.submit(prompts[2])
        assert server.queue_depth() == 2


class TestBatchServerSatellites:
    def test_batch_server_frees_prompt_buffers(self, tiny_cfg, tiny_params):
        server = ContinuousBatchingServer(tiny_cfg, tiny_params, max_slots=2,
                                          max_len=32)
        for p in _prompts(tiny_cfg, 3, seed=7):
            server.submit(p, max_new=2)
        server.run_until_drained()
        assert _no_prompt_buffers(server.pool)

    def test_batch_server_backpressure(self, tiny_cfg, tiny_params):
        server = ContinuousBatchingServer(tiny_cfg, tiny_params, max_slots=1,
                                          max_len=32, max_queue=1)
        server.submit(_prompts(tiny_cfg, 1)[0])
        with pytest.raises(AdmissionQueueFull):
            server.submit(_prompts(tiny_cfg, 1, seed=8)[0])


class TestLifetimeRegressions:
    """ISSUE 6 satellites: round clamping, stale-slot reuse, bounded
    bookkeeping, and the device arena row lifecycle wiring."""

    @pytest.mark.parametrize("server_cls", [SessionServer,
                                            ContinuousBatchingServer])
    def test_overlong_prompt_rejected_at_submit(self, tiny_cfg, tiny_params,
                                                server_cls):
        server = server_cls(tiny_cfg, tiny_params, max_slots=1, max_len=8)
        with pytest.raises(ValueError, match="prompt length"):
            server.submit(np.zeros(8, np.int32))  # max_len - 1 = 7
        server.submit(np.zeros(7, np.int32))  # exactly full cache: accepted

    @pytest.mark.parametrize("server_cls", [SessionServer,
                                            ContinuousBatchingServer])
    def test_negative_max_new_rejected(self, tiny_cfg, tiny_params,
                                       server_cls):
        server = server_cls(tiny_cfg, tiny_params, max_slots=1, max_len=8)
        with pytest.raises(ValueError, match="max_new"):
            server.submit(np.zeros(3, np.int32), max_new=-1)

    def test_max_new_zero_means_zero_rounds_session(self, tiny_cfg,
                                                    tiny_params):
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32)
        req = server.submit(_prompts(tiny_cfg, 1)[0], max_new=0)
        done = server.run_until_drained()
        server.close()
        assert [r.rid for r in done] == [req.rid]
        assert req.generated == []
        assert req.t_finish >= req.t_admit
        assert _no_prompt_buffers(server.pool)

    def test_full_prompt_gets_zero_rounds_session(self, tiny_cfg,
                                                  tiny_params):
        """A prompt filling the cache (len == max_len - 1) must NOT get the
        old forced decode round that pushed pos past max_len."""
        server = SessionServer(tiny_cfg, tiny_params, max_slots=1, max_len=8)
        req = server.submit(np.zeros(7, np.int32), max_new=5)
        server.run_until_drained()
        server.close()
        assert req.generated == []
        assert int(server.slots[0].value[2]) == 7  # pos never passed max_len-1

    def test_max_new_zero_means_zero_rounds_batch(self, tiny_cfg,
                                                  tiny_params):
        server = ContinuousBatchingServer(tiny_cfg, tiny_params, max_slots=2,
                                          max_len=32)
        req = server.submit(_prompts(tiny_cfg, 1)[0], max_new=0)
        done = server.run_until_drained()
        assert [r.rid for r in done] == [req.rid]
        assert req.generated == []
        assert not server.active and len(server.free) == 2

    def test_stale_slot_not_decoded_before_prefill(self, tiny_cfg,
                                                   tiny_params):
        """Regression: a freed slot kept its last occupant's (token, pos);
        re-granting it made the batch server schedule a decode against the
        stale token in the same step as the new prefill. After the reset,
        the admission step runs exactly the prefill."""
        server = ContinuousBatchingServer(tiny_cfg, tiny_params, max_slots=1,
                                          max_len=32)
        prompts = _prompts(tiny_cfg, 2, seed=8)
        server.submit(prompts[0], max_new=1)
        server.run_until_drained()  # request 0 done; slot 0 holds stale state
        req1 = server.submit(prompts[1], max_new=2)
        server.step()  # admission step for request 1
        assert server.report_log[-1]["tasks_this_run"] == 1  # prefill ONLY
        assert req1.generated == []  # nothing harvested from stale state
        server.run_until_drained()
        assert len(req1.generated) == 2

    def test_bookkeeping_is_bounded(self, tiny_cfg, tiny_params):
        """task_kinds drains with retirements; occupancy samples and the
        report log rotate at history_limit."""
        server = SessionServer(tiny_cfg, tiny_params, max_slots=2, max_len=32,
                               history_limit=4)
        for p in _prompts(tiny_cfg, 6, seed=10):
            server.submit(p, max_new=2)
        server.run_until_drained()
        server.close()
        assert server.task_kinds == {}
        assert len(server.occupancy_samples) <= 4
        assert len(server.report_log) <= 4
        assert server.occupancy_samples.maxlen == 4
        assert len(server.session.waves) <= 4

    def test_device_server_recycles_aux_rows_via_pool_free(self, tiny_cfg,
                                                           tiny_params):
        """pool.free on a device-server buffer releases its arena row (the
        free-hook wiring): recurring aux traffic reuses one bounded row
        set instead of leaking a row per buffer."""
        import jax.numpy as jnp

        from repro.core import Task
        from repro.core.task import default_segments

        server = SessionServer(tiny_cfg, tiny_params, max_slots=1, max_len=16,
                               scheduler="device")
        rows_after = []
        for wave in range(4):
            bufs = [server.pool.alloc((4,), np.float32,
                                      name=f"aux{wave}_{i}",
                                      value=jnp.full(4, float(i + 1)))
                    for i in range(3)]
            r, w = default_segments((bufs[0], bufs[1]), (bufs[2],))
            server.session.submit(
                Task(opcode="aux_axpy", fn=lambda x, y: x + 2.0 * y,
                     inputs=(bufs[0], bufs[1]), outputs=(bufs[2],),
                     read_segments=r, write_segments=w))
            server.session.flush()
            for b in bufs:
                server.pool.free(b.name)
            rows_after.append(server.session.arena.live_rows()
                              + server.session.arena.free_rows())
        assert rows_after[-1] == rows_after[0]  # flat, not 3 rows/wave
        assert server.session.arena.recycled_rows > 0
        server.close()


# ---------------------------------------------------------------------------
# Program shape: the weights are an operand of every serving program, never
# a closure constant (at published widths a constant would copy the whole
# model into each compiled prefill and decode program).
# ---------------------------------------------------------------------------

_CONST_TYPE = re.compile(r"stablehlo\.constant .*: tensor<([^>]*)>")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "i64": 8, "i32": 4,
                "ui32": 4, "i16": 2, "i8": 1, "ui8": 1, "i1": 1}


def _constant_bytes(hlo_text):
    total = 0
    for spec in _CONST_TYPE.findall(hlo_text):
        *dims, dtype = spec.split("x")
        total += int(np.prod([int(d) for d in dims])) * _DTYPE_BYTES[dtype]
    return total


class TestWeightsAsOperand:
    @pytest.fixture
    def programs(self):
        cfg = ARCHS["h2o-danube-3-4b"].reduced()
        params = init_params(cfg, jax.random.PRNGKey(0), tp_size=1)
        server = SessionServer(cfg, params, max_slots=1, max_len=32,
                               scheduler="device")
        slot = server.slots[0].value
        prefill = jax.jit(server._prefill_kernel.fn).lower(
            slot, server.weights.value, np.zeros((1, 8), np.int32))
        cache = slot[0]
        decode = jax.jit(server._decode_kernel.fn).lower(
            server.weights.value,
            (cache, np.zeros((1,), np.int32), np.int32(8)))
        server.close()
        return params, {"prefill": prefill, "decode": decode}

    @pytest.mark.parametrize("kind", ["prefill", "decode"])
    def test_weights_are_arguments_not_constants(self, programs, kind):
        params, lowered = programs
        lowered = lowered[kind]
        leaves = jax.tree.leaves(params)
        param_bytes = sum(leaf.nbytes for leaf in leaves)
        arg_shapes = [tuple(a.shape) for a in jax.tree.leaves(
            lowered.args_info)]
        for leaf in leaves:
            assert tuple(leaf.shape) in arg_shapes
        assert _constant_bytes(lowered.as_text()) < param_bytes / 100

    def test_non_finite_logits_yield_invalid_token(self):
        from repro.runtime.serve import INVALID_TOKEN, _next_token

        logits = np.zeros((2, 1, 8), np.float32)
        logits[0, 0, 3] = 1.0
        logits[1, 0, 5] = np.nan
        toks = np.asarray(_next_token(logits, 8))
        assert toks.tolist() == [3, INVALID_TOKEN]


def test_mesh_serving_outputs_committed_to_shard_devices():
    """Four forced host devices, one shard each: every slot's cache, token
    and position live on the device of the shard that ran its chain, the
    four slots land on four devices, each shard holds its own weight
    replica, and the tokens equal a one-device server's."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = """
import dataclasses
import re
import jax, numpy as np
from repro.configs import ARCHS
from repro.models import init_params
from repro.runtime import SessionServer

assert len(jax.devices()) == 4, jax.devices()
cfg = dataclasses.replace(ARCHS["h2o-danube-3-4b"].reduced(), n_layers=1)
params = init_params(cfg, jax.random.PRNGKey(0), tp_size=1)
rng = np.random.RandomState(0)
prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32) for n in (5, 7, 5, 7)]

def serve(**kw):
    server = SessionServer(cfg, params, max_slots=4, max_len=24, **kw)
    reqs = [server.submit(p, max_new=3) for p in prompts]
    server.run_until_drained()
    return server, [r.generated for r in reqs]

def devices_of(tree):
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}

mesh, tokens = serve(scheduler="mesh", n_shards=4)
_, ref = serve(scheduler="device")
assert tokens == ref, (tokens, ref)
session = mesh.session
seen = set()
for buf in mesh.slots:
    shard = session.shard_of(buf)
    dev = session.devices[shard]
    assert devices_of(buf.value) == {dev}, (buf.name, shard,
                                            devices_of(buf.value))
    seen.add(dev)
assert len(seen) == 4, seen
for shard in session._shards:
    replica = shard._replicas[id(mesh.weights)]
    assert devices_of(replica) == {shard.device}
assert session.session_stats()["transfer_mode"] == "d2d"
mesh.close()
print("MESH_SERVE_OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", script], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=150)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    assert "MESH_SERVE_OK" in proc.stdout
