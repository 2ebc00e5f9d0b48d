"""Mean ms of ``DeviceSession.flush`` per step in the window: the window's
dependency checks, planning, lowering and the loop executor's dispatch,
up to the state's return to the host."""


def read(ctx):
    if ctx.get("kind") != "sim":
        return None
    return ctx["spans"].mean_ms("flush")
