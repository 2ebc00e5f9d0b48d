"""Share, in %, of the decode program's roofline: the least time one
decode step could take on the chip (the larger of its operations over
peak FLOP/s and its bytes over peak bandwidth, from the configuration's
shapes at the positions decoded in the window) over the mean device time
of the decode program's events in the trace. The program is found by its
jit name; a trace without it is an error."""

import numpy as np

from bench import peaks

DECODE_PROGRAM = "_decode_fn"


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or ctx.get("kind") != "serve" or not ctx["decode_positions"]:
        return None
    n, seconds = tr.module_events(DECODE_PROGRAM)
    model, pk = ctx["model"], ctx["peaks"]
    pos = ctx["decode_positions"]
    flops = float(np.mean([peaks.decode_flops(model, p) for p in pos]))
    nbytes = float(np.mean([peaks.decode_bytes(model, p) for p in pos]))
    least = max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / n)
