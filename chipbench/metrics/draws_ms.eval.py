"""Device time per call of the ops of the draws layer."""


def read(ctx):
    return ctx.trace.layer_ms_per_call("draws")
