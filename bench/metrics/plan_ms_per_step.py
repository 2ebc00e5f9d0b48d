"""Self ms of ``acs.plan`` per step in the window: the window's drain in an
epoch (retire-and-refill, with the scoreboard inserts of the refill). Read
from the difference of the program's span table
(``session_stats()["spans"]``) across the window; None where the program has
no such span."""

NAMES = ("acs.plan",)


def read(ctx):
    if ctx.get("kind") != "sim" or not ctx.get("steps_in_window"):
        return None
    s0, s1 = (c.get("spans", {}) for c in ctx["counters"])
    if not any(n in s1 for n in NAMES):
        return None
    seconds = sum(s1[n]["self_s"] - s0.get(n, {}).get("self_s", 0.0)
                  for n in NAMES if n in s1)
    return 1e3 * seconds / ctx["steps_in_window"]
