"""Mean per call of the call's wall time less the device's busy time in it."""


def read(ctx):
    return ctx.trace.host_ms_per_call()
