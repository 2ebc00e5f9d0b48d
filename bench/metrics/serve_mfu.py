"""Model FLOP/s utilization of serving, in %: the operations that the
prompts admitted and the tokens handed over in the window require (from
the configuration's shapes), over the window times the chips times their
peak bf16 FLOP/s."""

from bench import peaks


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    model, pk = ctx["model"], ctx["peaks"]
    work = sum(peaks.decode_flops(model, p) for p in ctx["decode_positions"])
    work += sum(peaks.prefill_flops(model, n) for n in ctx["prefill_lengths"])
    if work == 0:
        return None
    return 100.0 * work / (ctx["window_s"] * ctx["chips"] * pk["bf16_flops"])
