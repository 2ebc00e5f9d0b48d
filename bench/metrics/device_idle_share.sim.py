"""Share of the traced window, in %, in which no operation ran on the
device (mean over the chips used), from the profiler trace."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    return 100.0 * tr.idle_share()
