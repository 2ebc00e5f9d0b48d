"""Self ms of ``sim.broadphase`` per step in the window: the host broadphase of
``emit_step``: each group's device-to-host state read and contact test. Read
from the difference of the program's span table
(``session_stats()["spans"]``) across the window; None where the program has
no such span."""

NAMES = ("sim.broadphase",)


def read(ctx):
    if ctx.get("kind") != "sim" or not ctx.get("steps_in_window"):
        return None
    s0, s1 = (c.get("spans", {}) for c in ctx["counters"])
    if not any(n in s1 for n in NAMES):
        return None
    seconds = sum(s1[n]["self_s"] - s0.get(n, {}).get("self_s", 0.0)
                  for n in NAMES if n in s1)
    return 1e3 * seconds / ctx["steps_in_window"]
