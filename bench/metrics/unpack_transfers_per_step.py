"""Device-to-host transfers of the read-back per step in the window: one
per shape class that ``arena.unpack`` moves to the host. Read from the
difference of the session's ``unpack_transfers`` counter across the
window; None where the program has no such counter."""


def read(ctx):
    if ctx.get("kind") != "sim" or not ctx.get("steps_in_window"):
        return None
    c0, c1 = ctx["counters"]
    if "unpack_transfers" not in c1:
        return None
    return (c1["unpack_transfers"] - c0["unpack_transfers"]) \
        / ctx["steps_in_window"]
