"""Device time per call of the failure law's own ops: those the reduced
trace puts in the program's `retry_draws` (the fates, and the split of the
attempts' keys) and `retry_transform` (each copy's busy time from its
attempts and fates).  The attempts' times themselves are draws, counted in
`draws_ms.eval`.  Nothing where the program has neither function."""

#: the last part of the qualified name of each function read
FUNCTIONS = ("retry_draws", "retry_transform")


def read(ctx):
    total = sum(t for key, t in ctx.trace.ops_s.items()
                if key.rpartition(" ")[2] in FUNCTIONS)
    return total * 1e3 / ctx.trace.n_calls if total else None
