"""The readers of the program's span table (``source: program_span``):
each reads the self time of its spans across the window, per step or per
token, answers None for the other kind of cell and for a program without
spans, and finds its spans in what the program records."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from _tiny_cells import BENCH

from bench import common

# reader -> (kind of cell, spans it sums)
READERS = {
    "plan_ms_per_step": ("sim", ("acs.plan",)),
    "lower_ms_per_step": ("sim", ("acs.lower",)),
    "launch_ms_per_step": ("sim", ("acs.launch", "acs.compile")),
    "sync_wait_ms_per_step": ("sim", ("acs.sync_wait",)),
    "unpack_ms_per_step": ("sim", ("acs.unpack",)),
    "broadphase_ms_per_step": ("sim", ("sim.broadphase",)),
    "token_read_ms_per_token": ("serve", ("serve.token_read",)),
    "host_task_ms_per_token": ("serve", ("acs.host_task",)),
    "retire_ms_per_token": ("serve", ("acs.retire",)),
    "admit_ms_per_token": ("serve", ("serve.admit",)),
}
PER = {"sim": "steps_in_window", "serve": "tokens_in_window"}


def _reader(name):
    return common.load_module(BENCH / "metrics" / f"{name}.py")


def _entry(self_s, n=1):
    return {"n": n, "total_s": 2 * self_s, "self_s": self_s}


def _ctx(kind, c0_spans, c1_spans, per=4):
    other = {"sim": "serve", "serve": "sim"}[kind]
    return {"kind": kind, PER[kind]: per, PER[other]: 0,
            "counters": ({"spans": c0_spans}, {"spans": c1_spans})}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_self_time_across_the_window(name):
    kind, names = READERS[name]
    # The first span was seen before the window; any second one is new in
    # it (a program built inside the window). Unrelated spans are ignored.
    c0 = {names[0]: _entry(0.5), "acs.epoch": _entry(9.0)}
    c1 = {n: _entry(0.9 if i == 0 else 0.2, n=3) for i, n in enumerate(names)}
    c1["acs.epoch"] = _entry(20.0)
    want = 1e3 * (0.4 + 0.2 * (len(names) - 1)) / 4
    assert _reader(name).read(_ctx(kind, c0, c1)) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_silent_where_it_has_nothing_to_read(name):
    kind, names = READERS[name]
    other = {"sim": "serve", "serve": "sim"}[kind]
    spans = {n: _entry(1.0) for n in names}
    reader = _reader(name)
    # the other kind of cell
    assert reader.read(_ctx(other, spans, spans)) is None
    # a program older than the span table
    no_spans = dict(_ctx(kind, {}, {}), counters=({}, {}))
    assert reader.read(no_spans) is None
    # a window with nothing in it
    assert reader.read(_ctx(kind, spans, spans, per=0)) is None


@pytest.fixture(scope="module")
def program_ctx():
    """Window contexts as the benchmark builds them, around a 64-env
    loop rollout and a tiny served model on the program itself."""
    import jax

    from repro.configs import ARCHS
    from repro.core import DeviceSession, TaskStream
    from repro.models import init_params
    from repro.runtime import SessionServer
    from repro.sim import ENVIRONMENTS, PhysicsEngine

    eng = PhysicsEngine(ENVIRONMENTS["cheetah"], n_envs=64, group_size=32,
                        seed=0)
    session = DeviceSession(plan_mode="loop")
    c0 = session.session_stats()
    for _ in range(2):
        stream = TaskStream()
        eng.emit_step(stream)
        session.submit(stream.tasks)
        session.flush()
    sim = {"kind": "sim", "steps_in_window": 2,
           "counters": (c0, session.session_stats())}
    session.close()

    cfg = dataclasses.replace(
        ARCHS["h2o-danube-3-4b"].reduced(), n_layers=1, d_model=32, d_ff=64,
        vocab=64, n_heads=2, n_kv_heads=1, head_dim=16)
    server = SessionServer(cfg, init_params(cfg, jax.random.PRNGKey(0),
                                            tp_size=1),
                           max_slots=2, max_len=32, scheduler="device")
    c0 = server.session.session_stats()
    rng = np.random.RandomState(0)
    for _ in range(3):
        server.submit(rng.randint(0, cfg.vocab, 5), max_new=3)
    done = server.run_until_drained()
    serve = {"kind": "serve",
             "tokens_in_window": sum(len(r.generated) for r in done),
             "counters": (c0, server.session.session_stats())}
    server.close()
    return {"sim": sim, "serve": serve}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_its_spans_in_the_program(program_ctx, name):
    kind, _ = READERS[name]
    value = _reader(name).read(program_ctx[kind])
    assert value is not None and value >= 0.0
