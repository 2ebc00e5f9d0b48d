"""Serving driver: continuous batching through the ACS window (DESIGN §4,
§10). Requests arrive over time; each owns a KV-cache slot; the ACS
dependency window automatically co-schedules new prefills with the
in-flight decode (disjoint slots => independent), while each request's own
prefill -> decode chain stays serialized by its RAW hazards.

Runs both servers on the same staggered arrivals: the live SessionServer
(admission emits prefills into the open window while the previous decode
group is still in flight) and the per-step batch-drain baseline.

    PYTHONPATH=src python examples/serve_continuous.py
"""

import dataclasses

import numpy as np

import jax

from repro.configs import ARCHS
from repro.models import init_params
from repro.runtime import ContinuousBatchingServer, SessionServer


ARRIVALS = {0: 2, 2: 1, 4: 2, 6: 1}  # iteration -> new requests


def run_batch(cfg, params, rng):
    server = ContinuousBatchingServer(cfg, params, max_slots=3, max_len=48)
    finished = []
    for it in range(40):
        for _ in range(ARRIVALS.get(it, 0)):
            req = server.submit(rng.randint(0, cfg.vocab, rng.randint(4, 9)),
                                max_new=6)
            print(f"[batch iter {it}] submitted request {req.rid}")
        for r in server.step():
            finished.append(r)
            print(f"[batch iter {it}] finished request {r.rid}: tokens {r.generated}")
        if not server.queue and not server.active and it > 8:
            break
    waves = server.report_log
    multi = sum(1 for e in waves if e.get("tasks_this_run", 0) > 1
                and e.get("waves_this_run", 0) < e.get("tasks_this_run", 0))
    print(f"batch: served {len(finished)} requests in {len(waves)} drains; "
          f"{multi} drains co-scheduled independent work in one wave\n")


def run_session(cfg, params, rng):
    server = SessionServer(cfg, params, max_slots=3, max_len=48,
                           scheduler="frontier")
    finished = []
    for it in range(120):
        for _ in range(ARRIVALS.get(it, 0)):
            req = server.submit(rng.randint(0, cfg.vocab, rng.randint(4, 9)),
                                max_new=6)
            print(f"[session pump {it}] submitted request {req.rid} "
                  f"(queue depth {req.queue_depth})")
        done = server.pump()
        for r in done:
            finished.append(r)
            print(f"[session pump {it}] finished request {r.rid}: tokens {r.generated}")
        if not server.queue and not server.active and it > 8:
            break
        if not done:
            server.session.drive()  # block only when nothing retired this pump
    report = server.close()
    print(f"session: served {len(finished)} requests; "
          f"{report.max_inflight_groups()} groups overlapped in flight; "
          f"retired by stream tag: {dict(sorted(server.session.retired_by_tag.items()))}")


def main():
    cfg = dataclasses.replace(
        ARCHS["h2o-danube-3-4b"].reduced(),
        n_layers=2, d_model=64, d_ff=128, vocab=512,
    )
    params = init_params(cfg, jax.random.PRNGKey(0), tp_size=1)
    run_batch(cfg, params, np.random.RandomState(0))
    run_session(cfg, params, np.random.RandomState(0))


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
