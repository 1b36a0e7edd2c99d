"""Least time of a call (its operations and bytes, counted from the cell's
sizes, at the chip's peaks) over the device's busy time per call, in %."""

from chipbench import roofline


def read(ctx):
    busy = ctx.trace.busy_s_per_call()
    if not busy:
        return None
    least, _ = roofline.least_time_s(ctx.cell, ctx.peaks())
    return 100.0 * least / busy
