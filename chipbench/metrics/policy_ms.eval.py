"""Device time per call of the ops of the policy-evaluation layer."""


def read(ctx):
    return ctx.trace.layer_ms_per_call("policy")
