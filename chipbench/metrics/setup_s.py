"""Process start to the window: JAX start-up, the inputs made from the
seed, and one warm call that compiles or loads every program."""


def read(ctx):
    return ctx.setup_s
