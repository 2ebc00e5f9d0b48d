"""Scoreboard probes per task submitted in the window, from the
session's ``scoreboard_probes`` counter."""


def read(ctx):
    if ctx.get("kind") != "sim" or not ctx.get("tasks_in_window"):
        return None
    c0, c1 = ctx["counters"]
    return (c1["scoreboard_probes"] - c0["scoreboard_probes"]) / ctx["tasks_in_window"]
