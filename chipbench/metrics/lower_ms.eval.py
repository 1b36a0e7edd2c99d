"""Per call, the device-idle time inside the program's `grid.lower` spans:
checking and lowering the grid and uploading its arguments."""

from chipbench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx.trace, "grid.lower")
