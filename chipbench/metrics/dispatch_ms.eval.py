"""Per call, the device-idle time inside the program's `grid.dispatch`
spans: the jitted call until it returns (a compile in the window shows here)."""

from chipbench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx.trace, "grid.dispatch")
