"""Per call, the device-idle time inside the program's `grid.tail` spans:
the percentiles and the row dicts."""

from chipbench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx.trace, "grid.tail")
