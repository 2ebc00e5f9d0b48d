"""Bring-up check: drive the ACS runtime's main paths once on a TPU, at size.

Run from the repository root, on a machine whose JAX sees TPU chips:

    python chip_smoke.py             # one chip: serving, simulation, ready queue
    python chip_smoke.py --chips=4   # four chips: the mesh phase only

One chip runs three phases through the entry points a user calls:

1. ``serving`` — h2o-danube-3-4b at its published widths (24 layers,
   d_model 3840, 32 heads / 8 KV heads of 120, d_ff 10240, vocab 32000,
   window 4096, bfloat16) with seeded random weights, served through
   ``SessionServer(scheduler="device")`` and again through
   ``scheduler="frontier"``; every request must finish with ``max_new``
   tokens in ``[0, vocab)`` (a non-finite logit yields an out-of-range
   token), and the two token streams must be identical;
2. ``sim`` — ``PhysicsEngine("cheetah")`` at paper scale (2048 envs in
   groups of 512) through ``DeviceSession(plan_mode="loop")``, against
   ``run_serial`` on a twin engine;
3. ``ready_queue`` — the ``LOOP_BRANCHES`` chain stream through
   ``DeviceWindowRunner(plan_mode="loop")``, which must pick the Pallas
   ready-queue kernel, and an epoch over the kernel's cap through a
   ``DeviceSession``, which must take the interpreter and count it; both
   equal to ``run_serial``.

``--chips=4`` runs only ``mesh``: the simulation stream through
``MeshDeviceSession(n_shards=4)`` against one ``DeviceSession``, and the
serving requests through ``SessionServer(scheduler="mesh", n_shards=4)``
(one weight replica per chip) against ``scheduler="device"`` on one chip.
The link must select ``d2d``, and the arrays the shards produced must sit
on four distinct devices.

Each phase prints one JSON line (device kind, wall and compile seconds,
peak device bytes, session counters, checks). The last line is
``{"ok": true, "device": {...}}``. Any failed check, or a JAX that finds
no TPU, exits non-zero without that line. Compiles are cached in JAX's
persistent cache (``repro.compile_cache``).
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "h2o-danube-3-4b"
SEED = 0
PROMPT_LENS = (128, 256, 128, 256)
MAX_NEW = 16
MAX_LEN = 512
SLOTS = 4
SIM_ENV, SIM_ENVS, SIM_GROUP, SIM_STEPS = "cheetah", 2048, 512, 3
# Tolerance for simulation states computed by two different compiled
# programs (an epoch interpreter vs per-kernel jit, or shard-sized epochs
# vs one whole epoch): the same float32 kernel bodies, but XLA may fuse
# and contract them differently, a few ulp (~1e-7 relative) per operation.
# Three Euler steps of ~20 operations per element stay far inside 1e-5.
SIM_RTOL, SIM_ATOL = 1e-5, 1e-6
# Ready-queue chain stream: 32 independent chains of 16 RAW-serialized
# axpy/mul kernels over 512-float rows — 512 tasks, inside the Pallas
# kernel's cap; the over-cap leg runs 2048 tasks (MAX_TASKS is 1024).
RQ_CHAINS, RQ_DEPTH, RQ_WIDTH = 32, 16, 512
RQ_OVER_CHAINS, RQ_OVER_DEPTH = 64, 32


class CompileClock:
    """Sums JAX's backend-compile durations (a persistent-cache hit is
    recorded as its short retrieval instead of a compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def _peak_bytes():
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.local_devices()]


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _pick(stats, keys):
    return {k: stats[k] for k in keys if k in stats}


SESSION_KEYS = ("plan_mode", "epochs", "device_dispatches", "loop_dispatches",
                "loop_pallas_over_cap", "host_task_dispatches",
                "plan_cache_hits", "plan_cache_misses", "compiled_programs",
                "host_syncs", "host_syncs_d2h", "host_syncs_h2d",
                "slab_bytes", "n_shards", "n_devices", "cross_shard_edges",
                "transfer_mode", "transfer_mode_reason", "d2d_moves",
                "staged_moves", "drain_overlap", "placements")


# -- phase 1: serving ---------------------------------------------------------

def make_model(cfg, seed: int = SEED):
    from repro.models import init_params

    params = init_params(cfg, jax.random.PRNGKey(seed), tp_size=1)
    jax.block_until_ready(params)
    return params


def make_prompts(cfg, lens=PROMPT_LENS, seed: int = SEED):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, size=n).astype(np.int32) for n in lens]


def serve(cfg, params, prompts, scheduler: str, max_new: int = MAX_NEW,
          max_len: int = MAX_LEN, **kw):
    """Serve ``prompts`` to completion; returns (token lists, server,
    session stats)."""
    from repro.runtime import SessionServer

    server = SessionServer(cfg, params, max_slots=SLOTS, max_len=max_len,
                           scheduler=scheduler, **kw)
    reqs = [server.submit(p, max_new=max_new) for p in prompts]
    server.run_until_drained()
    stats = (server.session.session_stats()
             if hasattr(server.session, "session_stats") else {})
    server.close()
    return [list(r.generated) for r in reqs], server, stats


def check_tokens(cfg, tokens, max_new: int) -> None:
    for i, toks in enumerate(tokens):
        _check(len(toks) == max_new,
               f"request {i}: {len(toks)} tokens, expected {max_new}")
        _check(all(0 <= t < cfg.vocab for t in toks),
               f"request {i}: token outside [0, {cfg.vocab}) — a non-finite "
               f"logit, or a bad argmax: {toks}")


def phase_serving(cfg, params, prompts, max_new: int = MAX_NEW,
                  max_len: int = MAX_LEN):
    dev_tokens, _, stats = serve(cfg, params, prompts, "device",
                                 max_new=max_new, max_len=max_len)
    check_tokens(cfg, dev_tokens, max_new)
    fr_tokens, _, _ = serve(cfg, params, prompts, "frontier",
                            max_new=max_new, max_len=max_len)
    _check(fr_tokens == dev_tokens,
           f"device and frontier token streams differ: {dev_tokens} vs "
           f"{fr_tokens}")
    return {"arch": cfg.name, "requests": len(prompts),
            "prompt_lens": [len(p) for p in prompts],
            "tokens_per_request": max_new,
            "device_equals_frontier": True,
            "first_tokens": [t[:4] for t in dev_tokens],
            "session_stats": _pick(stats, SESSION_KEYS)}


# -- phase 2: paper-scale simulation ------------------------------------------

def _engine(n_envs: int, group: int):
    from repro.sim import ENVIRONMENTS, PhysicsEngine

    return PhysicsEngine(ENVIRONMENTS[SIM_ENV], n_envs=n_envs,
                         group_size=group, seed=SEED)


def run_sim(session, n_envs: int, group: int, steps: int):
    """Step a fresh engine through ``session`` (None: ``run_serial``),
    syncing after each step — the broadphase reads the state on the host
    to choose the next step's contact kernels."""
    from repro.core import TaskStream, run_serial

    eng = _engine(n_envs, group)
    n_tasks = 0
    for _ in range(steps):
        stream = TaskStream()
        eng.emit_step(stream)
        n_tasks += len(stream.tasks)
        if session is None:
            run_serial(stream.tasks)
        else:
            session.submit(stream.tasks)
            session.flush()
    return eng, n_tasks


def compare_states(got, ref, what: str):
    """Exact equality, else the stated float32 tolerance; returns the
    comparison record."""
    got, ref = np.asarray(got), np.asarray(ref)
    _check(got.shape == ref.shape, f"{what}: shapes {got.shape} vs {ref.shape}")
    _check(bool(np.isfinite(got).all()), f"{what}: non-finite states")
    exact = bool(np.array_equal(got, ref))
    diff = float(np.max(np.abs(got - ref)))
    if not exact:
        np.testing.assert_allclose(got, ref, rtol=SIM_RTOL, atol=SIM_ATOL,
                                   err_msg=what)
    return {"exact": exact, "max_abs_diff": diff}


def phase_sim(n_envs: int = SIM_ENVS, group: int = SIM_GROUP,
              steps: int = SIM_STEPS):
    from repro.core import DeviceSession

    session = DeviceSession(plan_mode="loop")
    eng, n_tasks = run_sim(session, n_envs, group, steps)
    stats = session.session_stats()
    session.close()
    ref, _ = run_sim(None, n_envs, group, steps)
    cmp = compare_states(eng.state_snapshot(), ref.state_snapshot(),
                         "sim vs run_serial")
    _check(stats["loop_dispatches"] >= steps, "sim never took the loop path")
    return {"env": SIM_ENV, "n_envs": n_envs, "group_size": group,
            "steps": steps, "tasks": n_tasks, "vs_serial": cmp,
            "session_stats": _pick(stats, SESSION_KEYS)}


# -- phase 3: the Pallas ready queue ------------------------------------------

def chain_stream(n_chains: int, depth: int, width: int, seed: int = SEED):
    """``n_chains`` state rows, each the output of ``depth`` RAW-serialized
    LOOP_BRANCHES kernels that also read one shared weight row."""
    from repro.core import BufferPool, Task
    from repro.core.task import default_segments
    from repro.kernels.ops import LOOP_BRANCHES

    rng = np.random.RandomState(seed)
    pool = BufferPool()
    states = [pool.alloc((width,), np.float32, name=f"chain{i}",
                         value=jnp.asarray(rng.randn(width).astype(np.float32)))
              for i in range(n_chains)]
    weight = pool.alloc((width,), np.float32, name="weight",
                        value=jnp.asarray(rng.randn(width).astype(np.float32)))
    tasks = []
    for s in states:
        for d in range(depth):
            op = "axpy" if d % 2 == 0 else "mul"
            ins, outs = (s, weight), (s,)
            r, w = default_segments(ins, outs)
            tasks.append(Task(opcode=op, fn=LOOP_BRANCHES[op], inputs=ins,
                              outputs=outs, read_segments=r, write_segments=w))
    return states, tasks


def _snap(states):
    return np.stack([np.asarray(s.value) for s in states])


def phase_ready_queue(n_chains: int = RQ_CHAINS, depth: int = RQ_DEPTH,
                      width: int = RQ_WIDTH, over_chains: int = RQ_OVER_CHAINS,
                      over_depth: int = RQ_OVER_DEPTH):
    from repro.core import DeviceSession, DeviceWindowRunner, run_serial
    from repro.kernels.ops import register_loop_branches

    states, tasks = chain_stream(n_chains, depth, width)
    run_serial(tasks)
    ref = _snap(states)
    states, tasks = chain_stream(n_chains, depth, width)
    runner = DeviceWindowRunner(plan_mode="loop", window_size=64)
    register_loop_branches(runner.registry)
    report = runner.run(tasks)
    _check(report.loop_executor == "pallas",
           f"ready queue ran on {report.loop_executor!r}, not the Pallas kernel")
    got = _snap(states)
    _check(np.array_equal(got, ref),
           "Pallas ready queue differs from run_serial: max abs diff "
           f"{float(np.max(np.abs(got - ref)))}")

    # Over the cap: the session must fall back to the interpreter, count it,
    # and still match the serial baseline.
    states, tasks = chain_stream(over_chains, over_depth, width)
    run_serial(tasks)
    over_ref = _snap(states)
    states, tasks = chain_stream(over_chains, over_depth, width)
    session = DeviceSession(plan_mode="loop", window_size=64)
    register_loop_branches(session.registry)
    session.submit(tasks)
    session.flush()
    stats = session.session_stats()
    session.close()
    _check(stats["loop_pallas_over_cap"] >= 1,
           "over-cap epoch was not counted in loop_pallas_over_cap")
    got = _snap(states)
    _check(np.array_equal(got, over_ref),
           "over-cap interpreter epoch differs from run_serial")
    return {"tasks": n_chains * depth, "width": width,
            "loop_executor": report.loop_executor,
            "equals_serial": True,
            "over_cap_tasks": over_chains * over_depth,
            "over_cap_equals_serial": True,
            "session_stats": _pick(stats, SESSION_KEYS)}


# -- four chips: the mesh -----------------------------------------------------

def _devices_of(tree):
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def phase_mesh(cfg, params, prompts, n_shards: int = 4,
               n_envs: int = SIM_ENVS, group: int = SIM_GROUP,
               steps: int = SIM_STEPS, max_new: int = MAX_NEW,
               max_len: int = MAX_LEN):
    from repro.core import DeviceSession, MeshDeviceSession

    # Simulation: mesh shards vs one device session, same process.
    mesh = MeshDeviceSession(n_shards=n_shards)
    mesh_eng, n_tasks = run_sim(mesh, n_envs, group, steps)
    mesh_stats = mesh.session_stats()
    mesh.close()
    single = DeviceSession(plan_mode="loop")
    single_eng, _ = run_sim(single, n_envs, group, steps)
    single.close()
    sim_cmp = compare_states(mesh_eng.state_snapshot(),
                             single_eng.state_snapshot(),
                             "mesh sim vs one DeviceSession")
    sim_devices = set()
    for grp in mesh_eng.groups:
        sim_devices |= _devices_of(grp.state.value)
    _check(mesh_stats["transfer_mode"] == "d2d",
           f"sim mesh link selected {mesh_stats['transfer_mode']!r}: "
           f"{mesh_stats['transfer_mode_reason']}")
    _check(len(sim_devices) == n_shards,
           f"sim states produced on {len(sim_devices)} devices, expected "
           f"{n_shards}: {sorted(map(str, sim_devices))}")

    # Serving: mesh server (one weight replica per chip) vs one chip.
    ref_tokens, _, _ = serve(cfg, params, prompts, "device",
                             max_new=max_new, max_len=max_len)
    tokens, server, stats = serve(cfg, params, prompts, "mesh",
                                  n_shards=n_shards, max_new=max_new,
                                  max_len=max_len)
    check_tokens(cfg, tokens, max_new)
    _check(tokens == ref_tokens,
           f"mesh tokens differ from one chip: {tokens} vs {ref_tokens}")
    _check(stats["transfer_mode"] == "d2d",
           f"serving mesh link selected {stats['transfer_mode']!r}: "
           f"{stats['transfer_mode_reason']}")
    slot_devices = set()
    for buf in server.slots:
        slot_devices |= _devices_of(buf.value[0])
    _check(len(slot_devices) == n_shards,
           f"slot caches produced on {len(slot_devices)} devices, expected "
           f"{n_shards}: {sorted(map(str, slot_devices))}")
    return {"n_shards": n_shards,
            "sim": {"tasks": n_tasks, "vs_single": sim_cmp,
                    "devices": sorted(map(str, sim_devices)),
                    "session_stats": _pick(mesh_stats, SESSION_KEYS)},
            "serving": {"equals_one_chip": True,
                        "devices": sorted(map(str, slot_devices)),
                        "session_stats": _pick(stats, SESSION_KEYS)}}


# -- main ---------------------------------------------------------------------

def _run_phase(name: str, fn, clock: CompileClock, kind: str) -> None:
    t0 = time.perf_counter()
    c0, n0 = clock.seconds, clock.count
    record = fn()
    line = {"phase": name, "device_kind": kind,
            "wall_s": time.perf_counter() - t0,
            "compile_s": clock.seconds - c0, "compiles": clock.count - n0,
            "peak_bytes_in_use": _peak_bytes()}
    line.update(record)
    print(json.dumps(line, default=str), flush=True)


def main(argv) -> int:
    chips = 1
    for arg in argv:
        if arg.startswith("--chips="):
            chips = int(arg.split("=", 1)[1])
        else:
            print(f"unknown argument {arg!r}; usage: chip_smoke.py "
                  "[--chips=1|4]", file=sys.stderr)
            return 2
    if chips not in (1, 4):
        print(f"--chips must be 1 or 4, got {chips}", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform} ({dev})",
              file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"--chips={chips} but JAX sees {len(devices)} device(s)",
              file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.configs import ARCHS

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    kind = dev.device_kind
    print(json.dumps({"phase": "start", "device_kind": kind,
                      "devices": len(devices), "chips": chips,
                      "compile_cache": cache_dir}), flush=True)

    cfg = ARCHS[ARCH]
    t0 = time.perf_counter()
    c0 = clock.seconds
    params = make_model(cfg)
    prompts = make_prompts(cfg)
    print(json.dumps({"phase": "init", "device_kind": kind, "arch": cfg.name,
                      "params": int(sum(x.size for x in
                                        jax.tree.leaves(params))),
                      "wall_s": time.perf_counter() - t0,
                      "compile_s": clock.seconds - c0,
                      "peak_bytes_in_use": _peak_bytes()}), flush=True)
    if chips == 1:
        _run_phase("serving", lambda: phase_serving(cfg, params, prompts),
                   clock, kind)
        _run_phase("sim", phase_sim, clock, kind)
        _run_phase("ready_queue", phase_ready_queue, clock, kind)
    else:
        _run_phase("mesh", lambda: phase_mesh(cfg, params, prompts,
                                              n_shards=chips),
                   clock, kind)
    print(json.dumps({"phase": "total", "device_kind": kind,
                      "compile_s": clock.seconds, "compiles": clock.count}),
          flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
