"""A cell of the benchmark at a size a CPU test can hold: the same
configuration, traffic and entry, with narrower jobs and fewer of them."""

from __future__ import annotations

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

#: task counts at test size, by the configuration's task count
SMALL_N = {1026: 64, 488: 32}
CELLS = [w["name"] for w in harness.benchmark()["workloads"]]


def small_cell(name: str):
    """(workload, config, traffic) of cell `name`, shrunk."""
    workload, config, traffic = harness.find_cell(name)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    for st in config["stages"]:
        st["n"] = SMALL_N[st["n"]]
    config["m_trials"], config["n_jobs"] = 4, 32
    return workload, config, traffic
