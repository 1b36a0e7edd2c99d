"""Host time inside the program's own spans, read from the reduced trace.

Each grid evaluation of the planner opens four host spans on the
profiler's clock, one after the other: `grid.lower` (checking and lowering
the grid, uploading its arguments), `grid.dispatch` (the jitted call until
it returns), `grid.fetch` (the wait for the program and the copy of its
results to the host) and `grid.tail` (percentiles and rows).  For one of
those names, `idle_ms_per_call` is the time inside the window's spans of
that name during which the device runs nothing, summed, per window call.
Together they split `Reading.host_ms_per_call`; what they leave out is the
host code between the spans.
"""

from __future__ import annotations

import numpy as np

from chipbench.tracereduce import _overlap


def idle_ms_per_call(reading, name: str):
    """Per window call, the device-idle ms inside spans called `name`
    (the device time averaged over the chips, as for the call's host time);
    None where the trace holds no such span."""
    spans = [(max(s, reading.t0), min(e, reading.t1)) for n, s, e, _ in reading.host
             if n == name and s < reading.t1 and e > reading.t0]
    if not spans or not reading.merged:
        return None
    idle = sum((e - s) - np.mean([_overlap(m, s, e) for m in reading.merged.values()])
               for s, e in spans)
    return float(idle) * 1e-6 / reading.n_calls
