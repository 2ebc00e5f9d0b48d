"""Named host spans (``core/spans.py``): self time under nesting, spans
whose body raises, one stack per thread, and the names the device
session, the sim engine and the server record, as ``session_stats()``
reports them."""

import dataclasses
import sys
import threading

import jax
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core import DeviceSession, MeshDeviceSession, TaskStream, spans
from repro.core.spans import span
from repro.models import init_params
from repro.runtime import SessionServer
from repro.sim import ENVIRONMENTS, PhysicsEngine

SIM_SPANS = {"acs.epoch", "acs.plan", "acs.lower", "acs.launch",
             "acs.compile", "acs.retire", "acs.sync", "acs.sync_wait",
             "acs.unpack", "sim.emit", "sim.broadphase"}
SERVE_SPANS = {"acs.epoch", "acs.host_task", "acs.retire",
               "serve.token_read", "serve.admit"}


@pytest.fixture
def clock(monkeypatch):
    """A clock that moves only when the test moves it."""
    now = [0.0]
    monkeypatch.setattr(spans, "_clock", lambda: now[0])
    return now


def _delta(before, after, name):
    b = before.get(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
    a = after[name]
    return {k: a[k] - b[k] for k in ("n", "total_s", "self_s")}


def _ran(before, after):
    """Names whose count rose between two snapshots."""
    return {n for n in after if _delta(before, after, n)["n"] > 0}


def test_nested_spans_split_total_and_self_time(clock):
    before = spans.snapshot()
    with span("t.nest.outer"):
        clock[0] += 1.0
        with span("t.nest.inner"):
            clock[0] += 2.0
        with span("t.nest.inner"):
            clock[0] += 3.0
            with span("t.nest.leaf"):
                clock[0] += 0.5
        clock[0] += 4.0
    after = spans.snapshot()
    assert _delta(before, after, "t.nest.outer") == {
        "n": 1, "total_s": 10.5, "self_s": 5.0}
    assert _delta(before, after, "t.nest.inner") == {
        "n": 2, "total_s": 5.5, "self_s": 5.0}
    assert _delta(before, after, "t.nest.leaf") == {
        "n": 1, "total_s": 0.5, "self_s": 0.5}


def test_span_whose_body_raises_is_recorded(clock):
    before = spans.snapshot()
    with span("t.raise.outer"):
        with pytest.raises(ValueError):
            with span("t.raise.inner", rid=7):
                clock[0] += 2.0
                raise ValueError("retirement callback ended the window")
        clock[0] += 1.0
    after = spans.snapshot()
    assert _delta(before, after, "t.raise.inner") == {
        "n": 1, "total_s": 2.0, "self_s": 2.0}
    # The raising span left the stack: its parent's self time excludes it.
    assert _delta(before, after, "t.raise.outer") == {
        "n": 1, "total_s": 3.0, "self_s": 1.0}


def test_threads_keep_separate_stacks(clock):
    """A span another thread opens while this one is open is not its
    child: each thread's self time counts its own stack alone."""
    opened, other_done = threading.Event(), threading.Event()

    def worker():
        with span("t.thread.a"):
            opened.set()
            other_done.wait(10)
            clock[0] += 1.0

    before = spans.snapshot()
    th = threading.Thread(target=worker)
    th.start()
    assert opened.wait(10)
    with span("t.thread.b"):
        clock[0] += 5.0
    other_done.set()
    th.join(10)
    after = spans.snapshot()
    assert _delta(before, after, "t.thread.a") == {
        "n": 1, "total_s": 6.0, "self_s": 6.0}
    assert _delta(before, after, "t.thread.b") == {
        "n": 1, "total_s": 5.0, "self_s": 5.0}


def test_many_threads_lose_no_span():
    """More threads than cores close spans of one name at a short switch
    interval: the summed table counts every one."""
    n_threads, per_thread = 32, 500
    before = spans.snapshot()

    def worker():
        for _ in range(per_thread):
            with span("t.stress.outer"):
                with span("t.stress.inner"):
                    pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads)
    after = spans.snapshot()
    for name in ("t.stress.outer", "t.stress.inner"):
        assert _delta(before, after, name)["n"] == n_threads * per_thread
    outer = _delta(before, after, "t.stress.outer")
    inner = _delta(before, after, "t.stress.inner")
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-6)


def test_loop_rollout_records_every_sim_span():
    eng = PhysicsEngine(ENVIRONMENTS["cheetah"], n_envs=64, group_size=32,
                        seed=0)
    session = DeviceSession(plan_mode="loop")
    before = session.session_stats()["spans"]
    for _ in range(2):
        stream = TaskStream()
        eng.emit_step(stream)
        session.submit(stream.tasks)
        session.flush()
    after = session.session_stats()["spans"]
    session.close()
    assert SIM_SPANS <= _ran(before, after)
    assert _delta(before, after, "sim.emit")["n"] == 2
    # one broadphase per group and step
    assert _delta(before, after, "sim.broadphase")["n"] == 2 * 2
    for name in SIM_SPANS:
        d = _delta(before, after, name)
        assert 0.0 <= d["self_s"] <= d["total_s"] + 1e-9, (name, d)
    assert np.isfinite(eng.state_snapshot()).all()


def test_device_server_records_the_per_token_spans():
    cfg = dataclasses.replace(
        ARCHS["h2o-danube-3-4b"].reduced(), n_layers=1, d_model=32, d_ff=64,
        vocab=64, n_heads=2, n_kv_heads=1, head_dim=16)
    params = init_params(cfg, jax.random.PRNGKey(0), tp_size=1)
    server = SessionServer(cfg, params, max_slots=2, max_len=32,
                           scheduler="device")
    before = server.session.session_stats()["spans"]
    rng = np.random.RandomState(0)
    for _ in range(3):
        server.submit(rng.randint(0, cfg.vocab, 5), max_new=3)
    done = server.run_until_drained()
    after = server.session.session_stats()["spans"]
    server.close()
    assert SERVE_SPANS <= _ran(before, after)
    tokens = sum(len(r.generated) for r in done)
    assert tokens == 9
    assert _delta(before, after, "serve.token_read")["n"] == tokens
    assert _delta(before, after, "serve.admit")["n"] == 3


def test_mesh_session_reports_spans_once():
    eng = PhysicsEngine(ENVIRONMENTS["cheetah"], n_envs=16, group_size=8,
                        seed=1)
    session = MeshDeviceSession(window_size=16, n_shards=2)
    stream = TaskStream()
    eng.emit_step(stream)
    session.submit(stream.tasks)
    session.flush()
    stats = session.session_stats()
    session.close()
    assert "acs.epoch" in stats["spans"]
    assert stats["per_shard"] and all("spans" not in s
                                      for s in stats["per_shard"])
