"""Serving cells: a decoder model behind ``SessionServer``.

Set-up draws the weights on the device in one jitted call from the seed,
builds the server the configuration names, and warms every prompt length
the mix sends (one prefill program each) and the decode program. The
window then offers the mix's load: ``clients`` clients each send their
next request as soon as the last one finished; the window opens once
every slot is busy.

A token is timed when the server hands it over, which is the retirement
callback's append to ``Request.generated``: the benchmark gives each
request a list that stamps the time of each append.

After the window the peak memory is read, the program's state is freed,
and the reference checks a sample of finished requests drawn from the
seed, the longest among them.
"""

from __future__ import annotations

import functools
import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import common, traffic as traffic_gen

class StampedTokens(list):
    """A request's token list that records when each token was appended,
    and tells the client (``on_token``) at once: the client reacts at the
    hand-over itself, as a client thread would, not when the benchmark's
    loop next looks."""

    def __init__(self, max_new: int, on_token) -> None:
        super().__init__()
        self.times: List[float] = []
        self.max_new = max_new
        self.on_token = on_token

    def append(self, tok) -> None:
        self.times.append(time.perf_counter())
        super().append(tok)
        self.on_token(self)


class WindowClosed(Exception):
    """Raised from a hand-over after a closed loop's window: nothing after
    it is measured, so the run stops instead of finishing the session's
    epoch."""


def _server_class():
    from repro.runtime import SessionServer

    class Server(SessionServer):
        """The program's server; the prefill's retirement also keeps a
        handle on the token it produced (the program hands it to no
        client, and the check needs it). No value is read in the window."""

        def _on_prefill_retired(self, task, buf_name, slot, finish):
            req = self.active.get(slot)
            if req is not None:
                req.bench_first_token = self.slots[slot].value[1]
            super()._on_prefill_retired(task, buf_name, slot, finish)

    return Server


def arch_config(model: Dict[str, Any]):
    from repro.models.config import ArchConfig

    fields = dict(model)
    fields["pattern_unit"] = tuple(fields["pattern_unit"])
    return ArchConfig(family="dense", source="", **fields)


def make_weights(cfg, seed: int):
    import jax
    from repro.models import init_params

    init = jax.jit(functools.partial(init_params, cfg, tp_size=1))
    params = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


def _submit(server, req: traffic_gen.Request, on_token):
    r = server.submit(req.prompt, max_new=req.max_new)
    r.generated = StampedTokens(req.max_new, on_token)
    return r


def warm_up(server, lengths: List[int], vocab: int) -> None:
    """Serve one request of each prompt length with two tokens: every
    prefill length and the decode program compile here."""
    rng = np.random.RandomState(0)
    for n in lengths:
        server.submit(rng.randint(0, vocab, size=n).astype(np.int32),
                      max_new=2)
        server.run_until_drained()


def _serve(server, spans) -> None:
    """One turn of the service loop: pump, and block in the session when
    nothing finished."""
    with spans("pump"):
        done = server.pump()
    if not done and (server.active or server.queue):
        with spans("drive"):
            server.session.drive()


def _closed_loop(run, server, pool, clients, seconds, spans):
    """Each client sends its next request at the hand-over of its last
    token. Returns (requests, t0, t1)."""
    reqs = []
    window = {"t1": None}

    def on_token(toks):
        run.window_tick()
        if window["t1"] is not None and time.perf_counter() >= window["t1"]:
            raise WindowClosed
        if len(toks) == toks.max_new:
            send()

    def send():
        reqs.append(_submit(server, pool[len(reqs) % len(pool)], on_token))

    for _ in range(clients):
        send()
    server.pump()
    while len(server.active) < min(clients, len(server.slots)):
        server.session.drive()
        server.pump()
    t0 = time.perf_counter()
    window["t1"] = t1 = t0 + seconds
    spans.recording = True
    run.window_opened(t0)
    try:
        while time.perf_counter() < t1:
            _serve(server, spans)
            run.window_tick()
    except WindowClosed:
        pass
    spans.recording = False
    return reqs, t0, t1


def _tokens_in(reqs, t0, t1) -> int:
    return sum(sum(1 for t in r.generated.times if t0 <= t <= t1)
               for r in reqs)


def _sample(reqs, n: int, seed: int):
    """Up to ``n`` finished requests drawn from the seed, the one with the
    longest sequence always among them."""
    done = [r for r in reqs if r.finished and len(r.generated) == r.max_new
            and hasattr(r, "bench_first_token")]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.generated), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.RandomState(seed)
    pick = [longest] + [rest[i] for i in
                        sorted(rng.permutation(len(rest))[: n - 1])]
    return pick


def drive(run) -> Dict[str, Any]:
    cell = run.cell
    conf, mix = cell.config, cell.traffic
    model, srv = conf["model"], conf["server"]
    if mix["kind"] != "closed":
        raise common.BenchError(f"serving mixes are closed loops, not "
                                f"{mix['kind']!r}")
    cfg = arch_config(model)
    s_weights, s_traffic, s_sample = common.sub_seeds(run.seed, 3)
    spans = run.spans

    phases = {}
    t = time.perf_counter()
    params = make_weights(cfg, s_weights)
    phases["weights_s"] = time.perf_counter() - t
    Server = _server_class()
    server = Server(cfg, params, max_slots=srv["max_slots"],
                    max_len=srv["max_len"], window=srv["window"],
                    scheduler=srv["scheduler"], plan_mode=srv["plan_mode"])
    lengths = traffic_gen.prompt_lengths(mix)
    for n in lengths:
        if n > srv["max_len"] - 1 - mix["output"]["max"]:
            raise common.BenchError(f"prompt {n} + output {mix['output']['max']}"
                                    f" does not fit max_len {srv['max_len']}")
    phases["server_s"] = time.perf_counter() - t - phases["weights_s"]
    warm_up(server, lengths, cfg.vocab)
    phases["warm_up_s"] = (time.perf_counter() - t - phases["weights_s"]
                           - phases["server_s"])
    pool = traffic_gen.serving_requests(mix, s_traffic, cfg.vocab)
    run.setup_done()

    counters0 = server.session.session_stats()
    reqs, t0, t1 = _closed_loop(run, server, pool, mix["clients"],
                                run.seconds, spans)
    run.window_closed()
    counters1 = server.session.session_stats()
    peak = common.peak_bytes(run.devices)

    tokens = _tokens_in(reqs, t0, t1)
    ttft, itl = [], []
    for r in reqs:
        if r.generated.times:
            ttft.append(r.generated.times[0] - r.t_arrival)
        itl += list(np.diff(r.generated.times))
    invalid = sum(1 for r in reqs
                  if any(not 0 <= t < cfg.vocab for t in r.generated))
    invalid += sum(1 for r in reqs if hasattr(r, "bench_first_token")
                   and not 0 <= int(np.asarray(r.bench_first_token)[0]) < cfg.vocab)

    # Decode token positions in the window, for the bytes a decode reads.
    decode_positions = []
    for r in reqs:
        for i, t in enumerate(r.generated.times):
            if t0 <= t <= t1:
                decode_positions.append(len(r.prompt) + i)
    prefills = [len(r.prompt) for r in reqs if t0 <= r.t_admit <= t1]

    n_check = int(mix["check_requests"])
    length = -(-(max(lengths) + mix["output"]["max"]) // 128) * 128
    samples = _sample(reqs, n_check, s_sample)
    served = [(np.asarray(r.prompt),
               np.asarray([int(np.asarray(r.bench_first_token)[0])]
                          + list(r.generated), np.int32))
              for r in samples]
    served_tokens = sum(len(s) for _, s in served)

    window_s = t1 - t0
    pct = lambda xs, q: None if not xs else 1e3 * common.percentile(xs, q)
    notes = {
        "requests": len(reqs),
        "requests_finished": sum(1 for r in reqs if r.finished),
        "tokens_in_window": tokens, "window_s": window_s,
        "ttft_p50_ms": pct(ttft, 50), "ttft_p90_ms": pct(ttft, 90),
        "ttft_p99_ms": pct(ttft, 99), "itl_p50_ms": pct(itl, 50),
        "itl_p99_ms": pct(itl, 99), "itl_samples": len(itl),
        "setup_phases": phases,
        "check_requests": len(served), "check_served_tokens": served_tokens,
    }
    layer_ctx = {
        "kind": "serve", "chips": cell.chips, "model": model,
        "decode_positions": decode_positions,
        "tokens_in_window": tokens, "prefill_lengths": prefills,
        "window_s": window_s, "counters": (counters0, counters1),
        "spans": spans,
    }

    attempted = len(reqs)
    # Free the program's state before the reference runs.
    del server, params, reqs, pool, samples
    gc.collect()

    limit = conf["check"]["limits"]["max_logit_gap"]
    checks: Dict[str, Any] = {}
    if served:
        got = cell.reference().compare(model, s_weights, served, length,
                                       n_check, control=run.control)
        gap = got["max_logit_gap"]
        if run.control:
            # The control stands in the program's place: its tokens are
            # judged against the limit, the program's reading is a note.
            notes["program_max_logit_gap"] = gap
            gap = got["control_max_logit_gap"]
        checks["max_logit_gap"] = (gap, limit)
    checks["checked_requests"] = (len(served), ">= 1")
    checks["invalid_token_requests"] = (invalid, 0)
    correct = (bool(served) and invalid == 0
               and checks["max_logit_gap"][0] <= limit)
    return {"end_to_end": {"output_tokens_per_s": tokens / window_s},
            "layer_ctx": layer_ctx, "notes": notes,
            "checks": checks, "correct": correct,
            "attempted": attempted,
            "failed": invalid, "memory_peak_bytes": peak}
