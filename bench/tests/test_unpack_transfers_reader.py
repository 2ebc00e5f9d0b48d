"""The reader of the session's ``unpack_transfers`` counter (``source:
program_counter``): it reads the counter's difference across the window
per step, answers None for a serving cell, an empty window and a program
without the counter, and finds the counter in what the program records."""

from __future__ import annotations

import pytest

from _tiny_cells import BENCH

from bench import common


def _reader():
    return common.load_module(BENCH / "metrics"
                              / "unpack_transfers_per_step.py")


@pytest.mark.parametrize("c0, c1, kind, steps, want", [
    ({"unpack_transfers": 8}, {"unpack_transfers": 24}, "sim", 4, 4.0),
    ({}, {}, "sim", 4, None),
    ({"unpack_transfers": 8}, {"unpack_transfers": 24}, "serve", 4, None),
    ({"unpack_transfers": 8}, {"unpack_transfers": 8}, "sim", 0, None),
], ids=["counted", "program-without-counter", "serving-cell", "empty-window"])
def test_unpack_transfers_reader_reads_the_counter(c0, c1, kind, steps, want):
    ctx = {"kind": kind, "steps_in_window": steps, "counters": (c0, c1)}
    assert _reader().read(ctx) == want


def test_unpack_transfers_reader_finds_the_counter_in_the_program():
    """A window around two flushes of a 64-env loop rollout, as the
    benchmark builds it."""
    from repro.core import DeviceSession, TaskStream
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
    ctx = {"kind": "sim", "steps_in_window": 2,
           "counters": (c0, session.session_stats())}
    session.close()
    # Each flush of the rollout reads back four classes: state and ground
    # forces (one class), joint forces, contact forces, observations.
    assert _reader().read(ctx) == 4.0
