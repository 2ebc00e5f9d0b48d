"""The check that decides ``correct`` fails what it must: a run whose timed
path is broken underneath (a token altered where it is produced; a step
that returns its state unchanged or steps half of the group) reports ``correct: false``, and so does
a run with the control (the reference in the precision below the
configuration's) in the program's place, which reads well above the
program."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from _tiny_cells import BENCH, TINY_MODEL, dry_run, make_root

from bench import common

danube = common.load_module(BENCH / "reference" / "danube.py")
cheetah = common.load_module(BENCH / "reference" / "cheetah.py")
serve = common.load_module(BENCH / "drivers" / "serve.py")
CHEETAH = common.load_json(BENCH / "configs" / "cheetah-2048.json")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench-faults"))


def test_token_altered_where_produced_is_not_correct(tiny_root, capsys,
                                                     monkeypatch):
    import repro.runtime.serve as program

    produce = program._next_token
    monkeypatch.setattr(program, "_next_token",
                        lambda logits, vocab: (produce(logits, vocab) + 1) % vocab)
    rc, result, err = dry_run(tiny_root, "tiny-closed", 31, capsys)
    assert rc == 0
    assert result["correct"] is False
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert err.strip().splitlines()[-1] == "correct: False"


def test_step_that_leaves_the_state_unchanged_is_not_correct(tiny_root, capsys,
                                                             monkeypatch):
    from repro.sim import engine

    monkeypatch.setattr(engine._INTEGRATE, "fn", lambda state, *rest: state)
    rc, result, err = dry_run(tiny_root, "tiny-rollout", 32, capsys)
    assert rc == 0
    assert result["correct"] is False
    err_ = result["checks"]["max_step_error"]
    assert err_["value"] > err_["limit"]


def test_step_that_leaves_half_the_group_behind_is_not_correct(tiny_root,
                                                                capsys,
                                                                monkeypatch):
    """Half of each group's environments are stepped, the rest keep their
    state."""
    from repro.sim import engine

    step = engine._INTEGRATE.fn

    def half(state, *rest):
        new = step(state, *rest)
        keep = state.shape[0] // 2
        return jnp.concatenate([new[:keep], state[keep:]], axis=0)

    monkeypatch.setattr(engine._INTEGRATE, "fn", half)
    rc, result, err = dry_run(tiny_root, "tiny-rollout", 34, capsys)
    assert rc == 0
    assert result["correct"] is False
    err_ = result["checks"]["max_step_error"]
    assert err_["value"] > err_["limit"]


@pytest.mark.parametrize("workload,check", [
    ("tiny-closed", "max_logit_gap"),
    ("tiny-closed-local", "max_logit_gap"),
    ("tiny-rollout", "max_step_error"),
])
def test_control_in_the_programs_place_is_not_correct(tiny_root, capsys,
                                                      workload, check):
    """A run with the control in the program's place (the reference in the
    precision below the configuration's) fails the committed limit."""
    rc, result, err = dry_run(tiny_root, workload, 33, capsys, control=True)
    assert rc == 0
    assert result["correct"] is False
    got = result["checks"][check]
    assert got["value"] > got["limit"]
    program = result["notes"][f"program_{check}"]
    assert program <= got["limit"]
    assert err.strip().splitlines()[-1] == "correct: False"


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_reads_far_above_the_program(seed):
    """The float8 control's tokens lie further below the reference's best
    than the bfloat16 program's do, by a wide margin (at a test's size)."""
    from repro.models import forward

    m = common.load_json(BENCH / "configs" / "h2o-danube-3-4b.json")["model"]
    m.update(TINY_MODEL)
    cfg = serve.arch_config(m)
    params = serve.make_weights(cfg, seed)
    rng = np.random.RandomState(seed)
    samples = [(rng.randint(0, m["vocab"], 24).astype(np.int32),
                rng.randint(0, m["vocab"], 40).astype(np.int32))
               for _ in range(4)]
    logits = danube.forward_served(m, seed, samples, 64, 4, ("f32", "fp8"))
    program = []
    for prompt, served in samples:
        seq = np.concatenate([prompt, served[:-1]])[None]
        out = forward(params, cfg, jnp.asarray(seq), remat=False)
        program.append(np.asarray(out)[0, len(prompt) - 1:, :m["vocab"]])
    ref = logits["f32"]
    gap = lambda toks: float(jnp.max(danube._gaps(ref, toks)))
    program_gap = gap(jnp.argmax(jnp.asarray(np.concatenate(program)), -1))
    control_gap = gap(jnp.argmax(logits["fp8"], -1))
    assert control_gap > 5 * program_gap


def test_sim_control_fails_the_limit():
    from repro.core import TaskStream, run_serial
    from repro.sim import ENVIRONMENTS, PhysicsEngine

    conf = dict(CHEETAH, n_envs=32)
    eng = PhysicsEngine(ENVIRONMENTS["cheetah"], n_envs=32, group_size=8,
                        seed=9)
    states = {g: [np.asarray(grp.state.value)] for g, grp in enumerate(eng.groups)}
    for _ in range(4):
        stream = TaskStream()
        eng.emit_step(stream)
        run_serial(stream.tasks)
        for g, grp in enumerate(eng.groups):
            states[g].append(np.asarray(grp.state.value))
    got = cheetah.compare(conf, 9, 8, states, control=True)
    limit = CHEETAH["check"]["limits"]["max_step_error"]
    assert got["initial_state_error"] == 0.0
    assert got["max_step_error"] <= limit
    assert got["control_max_step_error"] > 3 * limit
