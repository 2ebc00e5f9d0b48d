"""Mean ms of ``PhysicsEngine.emit_step`` per step in the window (the
host broadphase and its device-to-host read of each group's state, plus
building the step's kernels)."""


def read(ctx):
    if ctx.get("kind") != "sim":
        return None
    return ctx["spans"].mean_ms("emit_step")
