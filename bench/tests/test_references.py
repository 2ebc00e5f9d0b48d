"""The plain references agree with the program at small sizes on the CPU:
the danube reference draws the served model's very weights from the seed
and gives its logits; the cheetah reference step gives the engine's step
and draws the engine's initial state and actions."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny_cells import BENCH, TINY_MODEL

from bench import common

danube = common.load_module(BENCH / "reference" / "danube.py")
cheetah = common.load_module(BENCH / "reference" / "cheetah.py")
serve = common.load_module(BENCH / "drivers" / "serve.py")
CHEETAH = common.load_json(BENCH / "configs" / "cheetah-2048.json")


def _model(dtype):
    m = common.load_json(BENCH / "configs" / "h2o-danube-3-4b.json")["model"]
    m.update(TINY_MODEL, n_layers=3, dtype=dtype)
    return m


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_reference_draws_the_served_weights(dtype):
    m = _model(dtype)
    m["tied_embeddings"] = False  # so that the output head is drawn too
    params = serve.make_weights(serve.arch_config(m), 1234)
    embed_key, head_key, keys = danube.layer_keys(1234, m["n_layers"])
    rows = params["embed"].shape[0]
    embed = danube.dense(embed_key, (rows, m["d_model"]), dtype)
    head = danube.dense(head_key, (m["d_model"], rows), dtype)
    assert embed.dtype == params["embed"].dtype
    for mine, served in ((embed, params["embed"]), (head, params["head"])):
        assert np.array_equal(np.asarray(mine, np.float32),
                              np.asarray(served, np.float32))
    dims = (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["d_ff"])
    layer = params["stages"][0]
    for i in range(m["n_layers"]):
        w = danube.layer_weights(keys[i], dims, dtype)
        for name in ("wq", "wk", "wv", "wo"):
            assert np.array_equal(np.asarray(w[name], np.float32),
                                  np.asarray(layer["mixer"][name][i], np.float32))
        for name in ("w_gate", "w_up", "w_down"):
            assert np.array_equal(np.asarray(w[name], np.float32),
                                  np.asarray(layer["ffn"][name][i], np.float32))


def test_reference_logits_match_the_program_forward():
    _logits_match(_model("float32"))


@pytest.mark.parametrize("kind", [
    dict(pattern_unit=["attn_local"], window=4, tied_embeddings=True),
    dict(pattern_unit=["attn_global"], window=None, tied_embeddings=False),
], ids=["sliding-window-tied", "global-untied"])
def test_reference_covers_other_decoders(kind):
    """A window shorter than the sequence binds; an untied output head is
    a matrix of its own."""
    m = _model("float32")
    m.update(kind)
    _logits_match(m)


def _logits_match(m):
    from repro.models import forward

    cfg = serve.arch_config(m)
    params = serve.make_weights(cfg, 99)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, m["vocab"], size=10).astype(np.int32)
    served = rng.randint(0, m["vocab"], size=6).astype(np.int32)
    got = danube.forward_served(m, 99, [(prompt, served)], 32, 2)["f32"]
    seq = np.concatenate([prompt, served[:-1]])[None]
    with jax.default_matmul_precision("highest"):
        want = forward(params, cfg, jnp.asarray(seq), remat=False)
    want = np.asarray(want)[0, len(prompt) - 1:, :m["vocab"]]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def _engine(n_envs, group, seed):
    from repro.sim import ENVIRONMENTS, PhysicsEngine

    return PhysicsEngine(ENVIRONMENTS["cheetah"], n_envs=n_envs,
                         group_size=group, seed=seed)


def test_cheetah_reference_draws_the_engine_inputs():
    from repro.core import TaskStream

    conf = dict(CHEETAH, n_envs=32)
    eng = _engine(32, 8, 77)
    init = cheetah.initial_state(conf, 77)
    assert np.array_equal(eng.state_snapshot(), init)
    acts = cheetah.actions(conf, 77, 8, 2)
    for step in range(2):
        eng.emit_step(TaskStream())
        for g in range(4):
            ctrl = eng.pool[f"ctrl{g}_s{step}"].value
            assert np.array_equal(np.asarray(ctrl), acts[step, g])


def test_cheetah_reference_step_matches_the_engine():
    from repro.core import TaskStream, run_serial

    conf = dict(CHEETAH, n_envs=32)
    eng = _engine(32, 8, 5)
    acts = cheetah.actions(conf, 5, 8, 6)
    for step in range(6):
        before = [np.asarray(g.state.value) for g in eng.groups]
        stream = TaskStream()
        eng.emit_step(stream)
        run_serial(stream.tasks)
        for g, grp in enumerate(eng.groups):
            ref = cheetah.step(conf, before[g], acts[step, g])
            np.testing.assert_allclose(np.asarray(grp.state.value), ref,
                                       rtol=1e-5, atol=1e-6)
