"""Per call, the device-idle time inside the program's `grid.fetch` spans:
from the program's end to the host holding its results."""

from chipbench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx.trace, "grid.fetch")
