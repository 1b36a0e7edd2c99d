"""Simulated jobs the window's calls covered, per second of the window."""


def read(ctx):
    return sum(r["jobs"] for r in ctx.records) / ctx.window_s
